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
sigmoid-scored, with a shared expert or without, told which experts of the
router's range it holds. It sorts the routings, runs grouped matmuls over the ragged
groups of those that land on the held experts, in buffers bounded by twice
their expected number (an exact overflow pass takes the rest, a buffer's
worth at a time), and sums the weighted results by token; it builds nothing
of size tokens x experts beyond the ``[T, E]`` scores.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu import megablox

from sav_tpu.models.layers.feedforward import GatedFFBlock, clamped_gate_up
from sav_tpu.ops import _backend
from sav_tpu.ops import attention as _attention
from sav_tpu.ops import rows_to_tokens as _sums
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
def _rows_of_tokens(x, token, live, sizes):
    """``x [T, D]`` -> ``[C, D]``: row ``r`` is token ``token[r]``'s where
    ``live[r]``, zeros elsewhere (``sizes``: the rows' groups, for the
    transpose). Its transpose is :func:`_tokens_of_rows`, on the cotangent's
    rows in the dtype they come in."""
    return jnp.where(live[:, None], jnp.take(x, token, axis=0), 0)


def _rows_of_tokens_fwd(x, token, live, sizes):
    return _rows_of_tokens(x, token, live, sizes), (token, live, sizes, x.shape[0])


def _rows_of_tokens_bwd(res, g):
    token, live, sizes, tokens = res
    return _tokens_of_rows(g, None, token, live, sizes, int(tokens), g.dtype), None, None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _tokens_of_rows(rows, weight, token, live, sizes, tokens, dtype):
    """``rows [C, D]`` -> ``[tokens, D]`` of ``dtype``: each token's float32
    sum of its ``live`` rows, each times its ``weight [C]`` float32 where one
    is given (what stands in the other rows is not read). ``sizes [held]`` are
    the live rows' groups, inside which ``token`` ascends. On a TPU one Mosaic
    call (``ops/rows_to_tokens.py``: the rows read once where they lie, in the
    dtype they have), elsewhere and at shapes Mosaic would not take XLA's scatter-add:
    :func:`~sav_tpu.ops.rows_to_tokens.sum_form` says which, and the dispatch
    log notes it. The transpose is :func:`_rows_of_tokens`, XLA's gather."""
    form = _sums.sum_form(rows.shape[0], tokens, rows.shape[1], sizes.shape[0], rows.dtype)
    _attention.log_sum_form(
        rows.shape, tokens, jnp.dtype(rows.dtype).name, jnp.dtype(dtype).name, weight is not None, form)
    if form["sum"] == "kernel":
        return _sums.rows_to_tokens(
            rows, weight, token, live, sizes, tokens=tokens, dtype=jnp.dtype(dtype), tile=form["tile"],
            unit=form["unit"], interpret=_backend.default_interpret(),
        )
    return _sums.sum_xla(rows, weight, token, live, tokens, dtype)


def _tokens_of_rows_fwd(rows, weight, token, live, sizes, tokens, dtype):
    return _tokens_of_rows(rows, weight, token, live, sizes, tokens, dtype), (rows, weight, token, live, sizes)


def _tokens_of_rows_bwd(tokens, dtype, res, g):
    rows, weight, token, live, sizes = res
    taken = _rows_of_tokens(g, token, live, sizes)
    if weight is None:
        return taken.astype(rows.dtype), None, None, None, None
    # d(float32(row) weight): the gathered cotangent times the other factor,
    # a dead row's factor masked (zero times what stands there is not zero).
    taken = taken.astype(jnp.float32)
    d_weight = jnp.sum(jnp.where(live[:, None], rows.astype(jnp.float32), 0.0) * taken, axis=1)
    return (weight[:, None] * taken).astype(rows.dtype), d_weight, None, None, None


_tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


def _fake_int8(a: jax.Array, contract_axes) -> jax.Array:
    """``a`` rounded to the int8 grid of its channel and back, with a
    straight-through gradient: the grouped matmuls' int8 arm."""
    q, scale = quantize_channelwise(a, contract_axes)
    rounded = (q.astype(jnp.float32) * scale).astype(a.dtype)
    return a + jax.lax.stop_gradient(rounded - a)


# Rows of a tile of the Mosaic grouped matmul, and the most a tile takes of
# either width.
GMM_ROW_TILE = 256
GMM_WIDTH_TILE = 1024


def gmm_tiling(dim: int, hidden: int) -> tuple:
    """(rows, contraction, columns) of a tile of the Mosaic grouped matmul for
    an expert of ``dim`` x ``hidden``: of each width the largest multiple of
    128 up to 1,024 that divides it (the width itself where none does). The
    tuple is fc1's, ``[rows, dim] x [dim, hidden]``, which it cuts into whole
    tiles; the layer's other matmuls and the transposes take the same tuple and
    meet the two widths in the other place, so fc2 ``[rows, hidden] x [hidden,
    dim]`` runs in partial tiles wherever the two tiles differ and do not
    divide each other's width (the kernel masks them). ``(256, 1024, 768)`` at
    2,048 x 768 was measured on a v5e at [65,536, 2,048] x [16, 2,048, 768]
    with about 256 rows a group (tools/moe_micro.py; PERF.md section 6, PR
    30). At 3,584 x 1,024 the rule gives ``(256, 896, 1024)``. Its 256 rows
    are measured there: one matmul of [8,192, 3,584] x [8, 3,584, 1,024] with
    every group in whole tiles reads 0.28 / 0.71 ms forward / with backward
    against 0.23 / 0.65 at a row tile of 512, but the layer (8 of 64 experts
    held, top-4, 8,192 tokens, groups of about 500 ragged rows) reads 7.55 /
    14.63 ms at 256 rows and 7.68 / 15.16 at 512, whose tiles meet two groups
    nearly everywhere. Its two width tiles are the rule's guess, not a tuned
    choice (PERF.md sections 6 and 7, PR 32)."""
    def tile(width: int) -> int:
        whole = [t for t in range(128, GMM_WIDTH_TILE + 1, 128) if width % t == 0]
        return max(whole, default=width)

    return GMM_ROW_TILE, tile(dim), tile(hidden)

# Rows the routed buffers hold, over the rows uniform routing would send to
# the experts held. The seeded layers of the published widths hold 0.78 to
# 1.19 of that expectation (the fullest seen 4,857 rows of 4,096; PERF.md
# section 5), so twice it holds them all; routing that leans onto the held
# experts (the benchmark cell's does within ten updates: up to 6 times the
# bound in its fullest layer) pays one more pass for every further buffer's
# worth of rows, so the factor trades rows moved in every step for passes
# taken in some. Read in that cell on a v5e at 1, 2, 4 and 8 (PERF.md
# section 6, PR 31): 5.81, 5.84, 5.71 and 5.25 sequences/s, the step's
# memory 14.53, 14.54, 14.84 and 15.23 GB.
ROWS_OVER_EXPECTED = 2


def routed_row_bound(routings: int, held: int, experts: int) -> int:
    """Rows of the routed buffers for ``routings`` (tokens x top_k) of which
    ``held`` of ``experts`` experts are here: ``ROWS_OVER_EXPECTED`` times the
    expected rows, whole tiles of the grouped matmul, never more than all of
    them (which is what holding every expert gives)."""
    tile = GMM_ROW_TILE
    return min(routings, tile * -(-ROWS_OVER_EXPECTED * routings * held // (experts * tile)))


def rows_over_bound(counts: jax.Array, routings: int, experts_held) -> jax.Array:
    """``counts [B, ..., E]`` (what :class:`SparseMoEBlock` returns, of layer
    applications of ``routings`` each) -> ``[...]``: the rows that landed on
    the experts held over the rows the routed buffers hold. Past 1 the
    overflow pass ran."""
    offset, held = experts_held or (0, counts.shape[-1])
    bound = routed_row_bound(routings, held, counts.shape[-1])
    return jnp.sum(counts[..., offset:offset + held], axis=(0, -1)) / bound


def grouped_matmul(rows: jax.Array, kernels: jax.Array, group_sizes: jax.Array, tiling: tuple) -> jax.Array:
    """``rows [R, K]`` in groups of consecutive rows, group ``e`` times
    ``kernels[e] [K, N]``, in tiles of ``tiling`` (:func:`gmm_tiling`). Rows
    past the last group belong to no held expert: no tile of theirs is
    computed and what stands there is not read.

    On a TPU a Mosaic grouped matmul (jax's megablox kernel, forward and both
    transposes), which keeps its caller's scope in the compiled step's
    ``op_name``; XLA's own lowering of ``ragged_dot`` is a Mosaic kernel too
    but names its calls ``ragged-dot-none``, and no reader of a trace could
    tell whose time that is. Elsewhere, and for a handful of rows that fill no
    tile (``init``'s trace), ``jax.lax.ragged_dot``."""
    if _backend.default_interpret() or rows.shape[0] % GMM_ROW_TILE:
        return jax.lax.ragged_dot(rows, kernels, group_sizes)
    return megablox.gmm(
        rows, kernels, group_sizes, rows.dtype, tiling, None, None, False, False
    )


class _StackedKernels(nn.Module):
    """Leaves with a leading axis of the experts held, as the grouped matmuls
    read them: in the compute dtype, on the int8 grid of their channel where
    ``quant``."""

    names: tuple
    shape: tuple
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self):
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=1, out_axis=2)
        kernels = (self.param(name, init, self.shape).astype(self.dtype) for name in self.names)
        return tuple(_fake_int8(kernel, (1,)) if self.quant else kernel for kernel in kernels)


class _RoutedExperts(nn.Module):
    """The routed experts' three stacked kernels ``(gate, up, down)``, under
    GatedFFBlock's scopes (``fc1``, ``fc2``); :func:`_expert_ffn` multiplies
    by them, in the common pass and in the overflow loops alike."""

    held: int
    hidden_ch: int
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, dim: int):
        gate, up = _StackedKernels(
            ("gate_experts_w1", "up_experts_w1"), (self.held, dim, self.hidden_ch), self.quant, self.dtype, name="fc1"
        )()
        (down,) = _StackedKernels(
            ("experts_w2",), (self.held, self.hidden_ch, dim), self.quant, self.dtype, name="fc2"
        )()
        return gate, up, down


def _expert_ffn(rows, kernels, group_sizes, quant, limit: float = 0.0):
    """``E_e(x) = W_down[e](silu(W_gate[e] x) * W_up[e] x)`` on sorted rows, in
    groups of ``group_sizes`` (GatedFFBlock's formula and scopes); where
    ``limit`` > 0 the two branches are clamped (``clamped_gate_up``)."""
    gate, up, down = kernels
    tiling = gmm_tiling(*gate.shape[1:])
    if quant:
        rows = _fake_int8(rows, (1,))
    with jax.named_scope("fc1"):
        hidden = clamped_gate_up(
            grouped_matmul(rows, gate, group_sizes, tiling), grouped_matmul(rows, up, group_sizes, tiling), limit)
    with jax.named_scope("fc2"):
        if quant:
            hidden = _fake_int8(hidden, (1,))
        return grouped_matmul(hidden, down, group_sizes, tiling)


def _sorted_chunk(order, group_sizes, index, rows):
    """Sorted positions ``[index rows, (index + 1) rows)`` of ``order``: the
    routings there, which of them land on a held expert (the first
    ``sum(group_sizes)`` positions do) and how many of each held expert's."""
    start = index * rows
    routing = jax.lax.dynamic_slice_in_dim(order, start, rows)
    live = start + jnp.arange(rows, dtype=jnp.int32) < jnp.sum(group_sizes)
    ends = jnp.clip(jnp.cumsum(group_sizes) - start, 0, rows)
    return routing, live, jnp.diff(ends, prepend=0)


def _routed_pass(quant, limit, k, rows, index, x, kernels, weights, order, group_sizes):
    """``[T, D]`` float32: what the sorted routings ``[index rows, (index + 1)
    rows)`` add to their tokens (scopes ``dispatch``, ``experts``,
    ``combine``). Their tokens' rows gathered, the held experts on their
    ragged groups, the weighted results summed by token. Rows past the last
    group are written by nobody, and the sum does not read them."""
    with jax.named_scope("dispatch"):
        routing, live, sizes = _sorted_chunk(order, group_sizes, index, rows)
        token = routing // k
        taken = _rows_of_tokens(x, token, live, sizes)
    with jax.named_scope("experts"):
        out = _expert_ffn(taken, kernels, sizes, quant, limit)
    with jax.named_scope("combine"):
        return _tokens_of_rows(out, jnp.take(weights, routing), token, live, sizes, x.shape[0], jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4), inline=True)
def _overflow_pass(quant, limit, k, rows, layer, chunks, routed, x, kernels, weights, order, group_sizes):
    """``[T, D]`` float32: ``routed`` (the common pass's result, pass 0) plus
    what passes ``1 .. chunks - 1`` over the sorted routings add, one
    :func:`_routed_pass` at a time in a loop of as many trips as there are
    such passes, summed onto ``routed`` where it lies: none where the
    held experts' rows fit the common pass. The backward pass is a loop of
    the same trips over the same operands, so what is not taken costs
    nothing in either direction but the zeros its cotangents start from.
    Both loops are jitted and inlined: a model's layers of one shape trace
    them once, and each call's operations keep its own scope. A loop puts
    ``while/body`` between its caller's scope and its body's, so the body
    opens the layer's own label ``layer`` again: a reader of a trace finds
    ``<layer>/dispatch|experts|combine`` in a trip as in the common pass."""
    def add(carry):
        index, y = carry
        with jax.named_scope(layer):
            added = _routed_pass(quant, limit, k, rows, index, x, kernels, weights, order, group_sizes)
        return index + 1, y + added

    return jax.lax.while_loop(lambda c: c[0] < chunks, add, (1, routed))[1]


def _overflow_pass_fwd(quant, limit, k, rows, layer, chunks, routed, *operands):
    return _overflow_pass(quant, limit, k, rows, layer, chunks, routed, *operands), (chunks, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4), inline=True)
def _overflow_pass_bwd(quant, limit, k, rows, layer, res, g):
    chunks, x, kernels, weights, order, group_sizes = res

    def add(carry):
        index, grads = carry
        with jax.named_scope(layer):
            added = jax.vjp(
                lambda *a: _routed_pass(quant, limit, k, rows, index, *a, order, group_sizes), x, kernels, weights
            )[1](g)
        return index + 1, jax.tree.map(jnp.add, grads, added)

    zeros = jax.tree.map(jnp.zeros_like, (x, kernels, weights))
    grads = jax.lax.while_loop(lambda c: c[0] < chunks, add, (1, zeros))[1]
    return (None, g, *grads, None, None)


_overflow_pass.defvjp(_overflow_pass_fwd, _overflow_pass_bwd)


SCORINGS = {"sigmoid": jax.nn.sigmoid, "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def kept_groups(biased: jax.Array, n_group: int, topk_group: int) -> jax.Array:
    """``biased [T, E]`` (score plus selection bias) -> ``[T, n_group]`` bool:
    the experts in ``n_group`` groups of consecutive ``E / n_group``, a group
    scored by the sum of its two largest entries, the ``topk_group`` best
    groups kept (arXiv:2412.19437's group-limited selection, as its public
    code scores a group)."""
    by_group = biased.reshape(biased.shape[0], n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, topk_group)
    return jnp.any(kept[..., None] == jnp.arange(n_group), axis=-2)


class _Router(nn.Module):
    """Scores over all ``num_experts`` (``scoring``: a sigmoid an expert, or
    a softmax over them), the top ``top_k`` of score plus selection bias, and
    the selected scores (without the bias) normalised to ``routed_scale``,
    over their sum plus ``weight_eps`` where a family adds one.

    With ``n_group`` > 1 the selection is group-limited (scope ``groups``):
    :func:`kept_groups` keeps ``topk_group`` of the ``n_group`` groups a
    token, and the top ``top_k`` are taken among the kept groups' experts
    alone (the others' entries at ``-inf``); scores, weights and the balance
    term read all ``num_experts`` as before. ``n_group`` 1 is the program
    without the step. Returns ``(scores, chosen, weights, kept)``: ``kept``
    the kept groups' mask ``[T, n_group]``, None without the step."""

    num_experts: int
    top_k: int
    routed_scale: float
    scoring: str = "sigmoid"
    weight_eps: float = 0.0
    n_group: int = 1
    topk_group: int = 1

    @nn.compact
    def __call__(self, x: jax.Array, select_bias: jax.Array):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (x.shape[-1], self.num_experts)
        )
        scores = SCORINGS[self.scoring](router_scores(x, kernel))  # [T, E] float32
        biased = scores + jax.lax.stop_gradient(select_bias)
        kept = None
        if self.n_group > 1:
            with jax.named_scope("groups"):
                kept = kept_groups(biased, self.n_group, self.topk_group)
                biased = jnp.where(jnp.repeat(kept, self.num_experts // self.n_group, axis=-1), biased, -jnp.inf)
        _, chosen = jax.lax.top_k(biased, self.top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        scaled = self.routed_scale * picked
        total = jnp.sum(picked, axis=-1, keepdims=True)
        weights = scaled / (total + self.weight_eps if self.weight_eps else total)
        # Tagged for a caller's remat policy: a few megabytes that spare the
        # backward pass the router's matmul and a second top-k.
        return *(checkpoint_name(t, "moe_route") for t in (scores, chosen, weights)), kept


class SparseMoEBlock(nn.Module):
    """Dropless mixture of experts, with a shared expert or (``shared_expert``
    False) without one, on ``[B, S, D]``.

    ``y = sum_{i selected and held} g_i E_i(x) + E_shared(x)``: every token
    scores all ``num_experts`` (``scoring``: ``sigmoid``, or ``softmax`` over
    all of them), the ``top_k`` of score plus ``select_bias`` are selected,
    ``g`` is the selected scores over their sum (plus ``weight_eps``) times
    ``routed_scale``. With
    ``shared_gate`` the shared expert's result is multiplied by ``sigmoid(x
    w_s)``, one number a token (leaf ``shared_gate/kernel``). Without a shared
    expert the sum is the routed part alone, ``y = sum_{i selected and held}
    g_i E_i(x)``: a token none of whose experts is held gets zeros, and the
    tree has no ``shared`` leaves.
    ``experts_held = (offset, count)`` names the experts whose weights live here (one chip's share of an expert-parallel
    layer; ``None`` holds all): the router, the counts and the balance loss
    stay ``num_experts`` wide, the routed leaves have a leading axis of
    ``count``, and what the absent experts would add is left out. No token
    is dropped at any routing: rows beyond the bound take the overflow pass.

    The path (scopes ``route``, ``dispatch``, ``experts``, ``combine``,
    ``overflow``, ``shared``): one stable sort of the ``T k`` routings by
    (expert, sequence) with the held experts first. The common pass works on
    the first ``C`` of them (:func:`routed_row_bound`: twice the rows uniform
    routing sends to the held experts, all ``T k`` where every expert is
    held): their tokens' rows gathered, grouped matmuls over the held
    experts' ragged groups cut off at ``C`` (rows past them are not read
    back), the weighted results summed by token. The largest tensors are
    ``[C, D]``. Where more than ``C`` routings land on the held experts, and
    only then, the overflow pass adds the others exactly: the same pass on
    the next ``C`` sorted routings, in a loop of as many trips as the rows
    need (none, as a rule); ``train/tasks.py`` logs how often
    (``moe_overflow_share``).

    ``n_group`` > 1 limits the selection to ``topk_group`` groups a token
    (:class:`_Router`); ``limit`` and ``shared_limit`` > 0 clamp the routed
    experts' and the shared expert's SwiGLU (``feedforward.py::
    clamped_gate_up``), 0 is the plain product.

    Returns ``(y, counts, balance, stats)``: ``counts [B, num_experts]`` float32,
    each sequence's routings by expert (no gradient: what the caller steps
    ``select_bias`` by), and ``balance``, the mean over sequences of
    ``sum_e f_e P_e`` with ``f_e = E / (k S) counts_e`` and ``P_e`` the mean
    of ``s_e / sum s`` (arXiv:2412.19437 eq. 17-20 at ``alpha`` 1);
    ``stats``, a dict of float32 scalars without a gradient: where the
    selection is group-limited ``groups_held``, the share of tokens whose
    kept groups include a group of the experts held (the others can send this
    chip nothing); empty otherwise.
    """

    num_experts: int
    top_k: int
    hidden_ch: int
    routed_scale: float = 1.0
    experts_held: Optional[Any] = None  # (offset, count); None = all
    scoring: str = "sigmoid"
    shared_expert: bool = True
    shared_gate: bool = False
    weight_eps: float = 0.0  # added to the selected scores' sum before the division
    n_group: int = 1  # groups of consecutive experts; > 1: the selection is limited to topk_group of them
    topk_group: int = 1
    limit: float = 0.0  # the routed experts' SwiGLU clamp; 0 = none
    shared_limit: float = 0.0  # the shared expert's
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array, select_bias: jax.Array):
        batch, seq, dim = inputs.shape
        experts, k = self.num_experts, self.top_k
        offset, held = self.experts_held or (0, experts)
        if not (0 <= offset and 0 < held and offset + held <= experts and k <= experts):
            raise ValueError(f"experts_held {self.experts_held} / top_k {k} do not fit {experts} experts")
        groups, per_group = self.n_group, experts // self.n_group
        if experts % groups or not 1 <= self.topk_group <= groups or self.topk_group * per_group < k:
            raise ValueError(f"{self.topk_group} of {groups} groups of {experts} experts do not hold top_k {k}")
        x = inputs.reshape(batch * seq, dim)

        scores, chosen, weights, kept = _Router(
            experts, k, self.routed_scale, self.scoring, self.weight_eps, groups, self.topk_group, name="route"
        )(x, select_bias)
        if self.shared_gate:
            # From the layer's input as the router reads it, in float32.
            opened = jax.nn.sigmoid(
                nn.Dense(1, use_bias=False, dtype=jnp.float32, name="shared_gate")(x.astype(jnp.float32))
            )
        x = x.astype(self.dtype)
        weights = weights.reshape(-1)  # one a routing, token-major like ``order``

        total = batch * seq * k
        bound = routed_row_bound(total, held, experts)
        with jax.named_scope("dispatch"):
            # Held experts become 0..held-1, so their routings sort first.
            local = (chosen - offset) % experts  # [T, k]
            sequence = jnp.arange(batch * seq, dtype=jnp.int32)[:, None] // seq
            keys = (local * batch + sequence).reshape(-1)
            order = jnp.argsort(keys, stable=True).astype(jnp.int32)
            edges = jnp.searchsorted(
                jnp.take(keys, order), jnp.arange(experts * batch + 1, dtype=keys.dtype)
            )
            by_local = jnp.diff(edges).reshape(experts, batch)  # [E, B], local order
            # The sort's result, for a caller's remat policy (two int32 vectors).
            order, by_local = (checkpoint_name(t, "moe_order") for t in (order, by_local))
            group_sizes = jnp.sum(by_local[:held], axis=1).astype(jnp.int32)

        kernels = _RoutedExperts(held, self.hidden_ch, self.quant, self.dtype, name="experts")(dim)
        # The common pass: the first ``bound`` sorted routings.
        routed = _routed_pass(self.quant, self.limit, k, bound, 0, x, kernels, weights, order, group_sizes)
        if bound < total:  # else every routing is in the buffers
            with jax.named_scope("overflow"):
                whole_passes = -(-total // bound) * bound
                routed = _overflow_pass(
                    self.quant, self.limit, k, bound, self.name or type(self).__name__,
                    -(-jnp.sum(group_sizes) // bound), routed, x, kernels, weights,
                    jnp.pad(order, (0, whole_passes - total)), group_sizes,
                )

        if self.shared_expert:
            shared = GatedFFBlock(
                hidden_ch=self.hidden_ch, quant=self.quant, dtype=self.dtype, limit=self.shared_limit, name="shared"
            )(x)
            with jax.named_scope("shared"):  # its sum onto the routed part is the shared expert's own
                shared = shared.astype(jnp.float32)
                if self.shared_gate:
                    shared = shared * opened
                routed = routed + shared
        y = routed.astype(self.dtype)

        with jax.named_scope("route"):
            counts = jnp.roll(by_local, offset, axis=0).T.astype(jnp.float32)  # [B, E]
            counts = jax.lax.stop_gradient(counts)
            share = scores / jnp.sum(scores, axis=-1, keepdims=True)
            mean_share = jnp.mean(share.reshape(batch, seq, experts), axis=1)  # [B, E]
            balance = jnp.mean(jnp.sum(counts * (experts / (k * seq)) * mean_share, axis=-1))
            stats = {}
            if kept is not None:
                ours = kept[:, offset // per_group:(offset + held - 1) // per_group + 1]
                stats["groups_held"] = jax.lax.stop_gradient(jnp.mean(jnp.any(ours, axis=-1).astype(jnp.float32)))
        return y.reshape(batch, seq, dim), counts, balance, stats
