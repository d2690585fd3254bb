"""Scaled dot-product attention cores with a pluggable TPU backend.

Functional equivalent of the einsum pipeline inside the reference's
``AttentionBlock`` (/root/reference/models/layers/attentions/attention.py:39-57):
``logits = einsum('...qhd,...khd->...hqk', q, k); softmax; einsum('...hqk,...khd->...qhd')``.

Layout convention everywhere in this framework: ``[batch..., length, heads, head_dim]``
(the natural output of ``nn.DenseGeneral`` head-splitting), matching the
reference. The flash kernel transposes to ``[B*H, L, D]`` internally; the
fused kernel reads ``[B, L, H*D]`` in place.

``backend``:
  - ``'xla'``    — jnp/einsum path, plain autodiff backward.
  - ``'fused'``  — single-pass fused short-sequence kernel
                   (:mod:`sav_tpu.ops.fused_attention`): the whole KV
                   sequence in one VMEM block, plain softmax (no online
                   carry), single fused backward, ``[B, L, H, D]`` read
                   and written in place. Raises when the shape exceeds the
                   single-block VMEM budget. Deterministic only.
  - ``'pallas'`` — blockwise online-softmax flash kernel
                   (:mod:`sav_tpu.ops.flash_attention`) for shapes beyond
                   the single block. Deterministic only (attention dropout
                   falls back to XLA).
  - ``'auto'``   — three-way measured dispatch on TPU (else xla), resolved
                   per traced shape by :func:`resolve_attention_backend`:

                   * dense fp32 logits past the HBM budget → ``pallas``
                     (the flash kernel's O(L·D) memory is the only way the
                     shape runs at all);
                   * short band (KV fits one VMEM block,
                     ``fused_attention.fused_eligible``) → the measured
                     winner from the ``tools/attn_tune.py`` cache
                     (:mod:`sav_tpu.ops.attn_tuning`) — ``fused`` only
                     where a chip run confirmed the win (L 197, D 64, H 6
                     and 12: PERF.md §6, PR 25), else XLA; never ``fused``
                     in a program XLA partitions over several devices
                     (:func:`partitioned_over`);
                   * middle band → ``xla`` (L² fits HBM comfortably and
                     XLA keeps the MXU busy).

                   A ``causal`` core (decoder self-attention) follows the
                   same rule on the cache's ``.causal`` entries, never
                   resolves to ``fused``, masks by an iota comparison on
                   the dense path and skips the blocks above the diagonal
                   in the flash kernel. A ``window`` beside it (a
                   sliding-window layer: ``i - window < j <= i``) is the
                   same comparison with a far edge, the blocks behind it
                   skipped too; it reads the cache's ``.causal.window<W>``
                   entries and never a causal entry's blocks, which were
                   chosen for another amount of work.

                   Every resolution is recorded in a trace-time dispatch
                   log (:func:`snapshot_dispatch_log`) that ``bench.py``
                   stamps into its JSON line and run manifest, so perf
                   history is attributable to the dispatch decision.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from sav_tpu.ops import attn_tuning
from sav_tpu.ops import flash_attention as _flash
from sav_tpu.ops import fused_attention as _fused


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:  # pragma: no cover - no backend at all
        return False


# 'auto' flips to the flash kernel when materializing the [B, H, Lq, Lk]
# fp32 logits (fwd + bwd residual ≈ 3 copies) would eat this much HBM —
# beyond it the XLA path thrashes or OOMs while flash stays O(L·D).
_AUTO_PALLAS_LOGITS_BYTES = 2 << 30

# DEPRECATED process-wide fallback for the XLA path's softmax dtype, used
# only when a caller passes ``logits_dtype=None`` to the bare functional
# core. Every framework path resolves the dtype explicitly instead: the
# attention *blocks* carry a ``logits_dtype`` attribute (None = the block's
# compute dtype — the reference's semantics) threaded from
# ``TrainConfig.attention_logits_dtype`` through ``create_model``, so no
# jitted model path reads this module state. f32 is the safe raw-op
# default; bf16 halves the dominant HBM traffic of the [B, H, L, L]
# logits/probability tensors (PERF.md §5) at ~2⁻⁸ relative logit precision.
_DEFAULT_LOGITS_DTYPE = jnp.float32


def set_default_logits_dtype(dtype) -> None:
    """DEPRECATED: set the process-wide softmax dtype fallback.

    Only affects direct :func:`xla_attention` / :func:`dot_product_attention`
    calls that pass ``logits_dtype=None``. Model blocks resolve their dtype
    from their own ``logits_dtype``/``dtype`` attributes and never consult
    this. Prefer passing ``logits_dtype`` explicitly.
    """
    global _DEFAULT_LOGITS_DTYPE
    _DEFAULT_LOGITS_DTYPE = jnp.dtype(dtype).type


# How many devices XLA partitions the program being traced over. A Mosaic
# call cannot be partitioned automatically: the step would fail to lower.
_TRACE = threading.local()


@contextlib.contextmanager
def partitioned_over(num_devices: int):
    """Trace-time only: while the ``with`` block traces a program, ``auto``
    knows that XLA will partition it over ``num_devices`` and promotes no
    measured ``fused`` entry on more than one (wrapping the kernel in
    ``shard_map`` is what would lift this). The trainer and the serve
    engine trace their steps inside it with their mesh's size; a bare call
    counts as one device."""
    previous = getattr(_TRACE, "devices", 1)
    _TRACE.devices = num_devices
    try:
        yield
    finally:
        _TRACE.devices = previous


def _dense_logits_bytes(batch: int, heads: int, q_len: int, kv_len: int) -> int:
    """HBM bytes of the dense attention's fp32 [B, H, Lq, Lk] working set
    (logits + probabilities + saved bwd residual ≈ 3 copies) — the single
    source of the ``auto`` rule's long-band accounting."""
    return 3 * 4 * batch * heads * q_len * kv_len


def xla_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    logits_dtype=None,
    causal: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Reference attention core in pure XLA ops.

    Args:
      query: ``[..., q_len, heads, head_dim]``.
      key, value: ``[..., kv_len, kv_heads, head_dim]``; ``kv_heads`` divides
        ``heads`` and query head ``h`` reads key/value head ``h // (heads /
        kv_heads)`` (grouped-query attention).
      bias: optional logits bias broadcastable to ``[..., heads, q_len, kv_len]``.
      scale: logit scale; defaults to ``head_dim ** -0.5`` (attention.py:39).
      logits_dtype: dtype for softmax math; None = the process default
        (:func:`set_default_logits_dtype`, f32 unless configured). fp32
        keeps bf16 runs stable; bf16 halves the L² HBM traffic.
      causal: position ``i`` attends to ``j <= i`` (self-attention:
        ``q_len == kv_len``). The mask is an iota comparison inside the
        program, never an ``[L, L]`` bias operand.
      window: with ``causal``, ``i`` attends to ``i - window < j <= i``.

    Returns:
      ``[..., q_len, heads, head_dim]`` in the query dtype.
    """
    if scale is None:
        scale = query.shape[-1] ** -0.5
    if logits_dtype is None:
        logits_dtype = _DEFAULT_LOGITS_DTYPE
    # Canonicalize: config-layer callers pass strings ('bfloat16').
    logits_dtype = jnp.dtype(logits_dtype)
    if key.shape[-2] != query.shape[-2]:  # grouped heads: the dense path repeats them
        group = query.shape[-2] // key.shape[-2]
        key, value = jnp.repeat(key, group, axis=-2), jnp.repeat(value, group, axis=-2)
    probs = _softmax_probs(query, key, bias, scale, logits_dtype, causal, window)
    if dropout_rate > 0.0 and not deterministic:
        if dropout_rng is None:
            raise ValueError("dropout_rng required for non-deterministic attention dropout")
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep.astype(probs.dtype) / (1.0 - dropout_rate)
    probs = probs.astype(value.dtype)
    return jnp.einsum("...hqk,...khd->...qhd", probs, value)


def causal_mask(q_len: int, kv_len: int, window: Optional[int] = None) -> jax.Array:
    """``[q_len, kv_len]`` bool, true where a query may look: ``j <= i`` and,
    under a ``window``, ``j > i - window`` (the kernels' own comparison:
    ``flash_attention.band_keep``)."""
    if q_len != kv_len:
        raise ValueError(
            f"causal attention is self-attention: q_len {q_len} != kv_len {kv_len}"
        )
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} positions")
    return _flash._causal_keep(0, 0, q_len, kv_len, window=window)


def _softmax_probs(q, k, bias, scale, logits_dtype, causal=False, window=None):
    """Scaled-QK softmax: the dense path's forward numerics."""
    qs = q * jnp.asarray(scale, dtype=q.dtype)
    logits = jnp.einsum(
        "...qhd,...khd->...hqk", qs, k, preferred_element_type=logits_dtype
    )
    if bias is not None:
        logits = logits + bias.astype(logits_dtype)
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    if causal:
        mask = causal_mask(logits.shape[-2], logits.shape[-1], window)
        logits = jnp.where(mask, logits, jnp.asarray(-jnp.inf, logits.dtype))
    return jax.nn.softmax(logits, axis=-1)


@dataclasses.dataclass(frozen=True)
class AttentionDispatch:
    """One resolved dispatch decision (static-shape, trace-time)."""

    backend: str  # 'xla' | 'fused' | 'pallas'
    reason: str  # human-readable why
    source: str  # 'requested' | 'threshold' | 'tuned' | 'default'
    block_config: Optional[dict] = None  # kernel block kwargs, if any

    def as_note(self) -> dict:
        return dataclasses.asdict(self)


# Trace-time dispatch provenance, keyed by (shape, requested backend) so
# bench.py / fit() can stamp *which* backend + block config each traced
# attention shape resolved to. Host-side and append-once-per-trace — the
# jitted hot path never touches it (savlint-clean by construction).
_DISPATCH_LOG: dict = {}
_DISPATCH_LOCK = threading.Lock()


def clear_dispatch_log() -> None:
    with _DISPATCH_LOCK:
        _DISPATCH_LOG.clear()


def snapshot_dispatch_log() -> list:
    """Resolved decisions since the last clear, one dict per unique
    (shape, requested) pair — the provenance record bench.py stamps into
    its JSON line and run manifest."""
    with _DISPATCH_LOCK:
        return [dict(v) for v in _DISPATCH_LOG.values()]


def _log_dispatch(shape, kv_len, kv_heads, requested, dispatch: AttentionDispatch, flash_forms=None,
                  window: Optional[int] = None) -> None:
    # kv_len is part of the identity: cross-attention sites share a query
    # shape with self-attention ones but can resolve differently; so is the
    # window: a banded core and a causal one of one shape are two records.
    _log_once((shape, kv_len, kv_heads, requested, window), {
        "shape": list(shape),
        "kv_len": kv_len,
        "kv_heads": kv_heads,
        "requested": requested or "auto",
        # A banded core's window (absent from a record without one) and,
        # with the flash kernel, among the forms below: 'band', the kernels
        # that run it ('resident': the pair whose grid is the band, a q
        # block and key/value head a cell; 'skipped_cells': the causal
        # kernels' arm, which skips the cells outside it), and a head's
        # counts of (q block, kv block) pairs: 'kv_blocks_visited' with work
        # under the window, 'kv_blocks_causal' under the causal mask alone,
        # 'kv_blocks_grid' spanned by the grid (the causal square, or the
        # resident blocks of every q block, the clipped ones before a
        # sequence's start among them), 'flush_cells' (resident: the
        # backward's cells past a sequence's end, which write dk and dv).
        **({} if window is None else {"window": window}),
        **dispatch.as_note(),
        # The flash kernel's forms: 'backward' ('one_kernel' |
        # 'two_kernels', the unbiased path's), 'layout' ('in_place' |
        # 'head_major') and, where the key/value heads are fewer than
        # the query's, 'grouped_kv' ('index_maps': the kernels find a
        # group's head through their block index | 'repeated': the
        # head-major copies repeat it | 'in_cell': the resident pair holds
        # a group's query heads in the cell of their key/value head).
        **(flash_forms or {}),
    })


def _log_once(key, record: dict) -> None:
    with _DISPATCH_LOCK:
        _DISPATCH_LOG.setdefault(key, record)


def log_rule_form(shape, value_heads: int, chunk: int, dtype: str, form: dict) -> None:
    """The gated delta rule's record (``ops/gated_delta.py::rule_form``) in
    the same log, one a traced shape: ``op``, the ``[B, L, H_k, d_k]`` shape
    of q and k, the value heads, the chunk, and the form: ``rule`` (``kernel``
    | ``xla``) with its ``chunk_tile``, or with what ``refused`` the kernels;
    ``decay: vector`` beside either where the decay is one a key lane (its
    kernels are ``_prepare_by_lane_in_vmem``'s); and who computes the rule's
    operands (the normalisation of q and k, a vector gate and its running
    sum): ``operands`` ``kernel`` with its ``operands_tile``, ``xla`` with
    ``operands_refused``, or ``given`` where the caller normalised."""
    key = ("gated_delta_rule", shape, value_heads, chunk, dtype, form["rule"], form.get("decay"), form["operands"])
    _log_once(key, {
        "op": "gated_delta_rule", "shape": list(shape), "value_heads": value_heads,
        "chunk": chunk, "dtype": dtype, **form,
    })


def resolve_attention_backend(
    batch: int,
    q_len: int,
    kv_len: int,
    heads: int,
    dim: int,
    *,
    dtype="bfloat16",
    requested: Optional[str] = None,
    kernels_ok: bool = True,
    on_tpu: Optional[bool] = None,
    num_devices: Optional[int] = None,
    causal: bool = False,
    value_dim: Optional[int] = None,
    window: Optional[int] = None,
) -> AttentionDispatch:
    """The three-way ``auto`` rule on static shapes (see module docstring).

    ``kernels_ok`` is the caller's eligibility for the Pallas paths (4-D
    inputs, deterministic); ``on_tpu`` defaults to the live backend and
    ``num_devices`` to the enclosing :func:`partitioned_over` (one without
    it). Every threshold here is test-pinned
    (tests/test_attn_dispatch.py). Explicit ``requested`` backends pass
    through, picking up any tuned block config for the shape. A ``causal``
    core reads the cache's ``.causal`` entries only, and is never ``fused``
    (the single-pass kernel has no mask); nor is a core whose value head
    (``value_dim``) is not the query's, which has cache entries of its own.
    A banded core (``window``) reads ``.causal.window<W>`` entries only:
    without one it takes the rule's verdict and the kernel's default blocks.
    """
    if on_tpu is None:
        on_tpu = _on_tpu()
    if num_devices is None:
        num_devices = getattr(_TRACE, "devices", 1)
    entry = attn_tuning.lookup(
        batch, q_len, kv_len, heads, dim, dtype, causal=causal, value_dim=value_dim, window=window
    )
    tuned_cfg = attn_tuning.block_config(entry)
    if requested and requested != "auto":
        cfg = tuned_cfg if (entry and entry["backend"] == requested) else None
        return AttentionDispatch(
            backend=requested, reason="explicit backend", source="requested",
            block_config=cfg,
        )
    if not kernels_ok or not on_tpu:
        return AttentionDispatch(
            backend="xla",
            reason=(
                "kernel-ineligible call (dropout or non-4-D inputs)"
                if not kernels_ok
                else "non-TPU backend"
            ),
            source="threshold",
        )
    itemsize = jnp.dtype(dtype).itemsize
    dense_bytes = _dense_logits_bytes(batch, heads, q_len, kv_len)
    if dense_bytes > _AUTO_PALLAS_LOGITS_BYTES:
        cfg = tuned_cfg if (entry and entry["backend"] == "pallas") else None
        return AttentionDispatch(
            backend="pallas",
            reason=(
                f"dense fp32 logits ≈{dense_bytes >> 20} MiB exceed the "
                f"{_AUTO_PALLAS_LOGITS_BYTES >> 30} GiB HBM budget"
            ),
            source="threshold",
            block_config=cfg,
        )
    short = not causal and value_dim in (None, dim) and _fused.fused_eligible(
        q_len, kv_len, dim, heads=heads, itemsize=itemsize
    )
    if entry:
        # A measured winner from the tune cache. Fused is additionally
        # gated on the VMEM band (a fused verdict at an over-budget shape
        # is stale/foreign — ignore it) and on a program of one device (a
        # Mosaic call cannot be partitioned: the dense path runs as it did
        # before the entry was measured); xla and pallas verdicts apply at
        # any shape the sweep measured.
        winner = entry["backend"]
        if winner == "fused" and (not short or num_devices > 1):
            winner = None
        if winner:
            return AttentionDispatch(
                backend=winner,
                reason=(
                    f"measured {winner} win "
                    f"({entry.get('source', 'tune cache')})"
                ),
                source="tuned",
                block_config=tuned_cfg if winner != "xla" else None,
            )
    return AttentionDispatch(
        backend="xla",
        reason=(
            "short band, no measured fused win to promote (promotion is "
            "gated on a chip measurement of the shape, on one device)"
            if short
            else "middle band: dense logits fit HBM, XLA keeps the MXU busy"
        ),
        source="default",
    )


def dot_product_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    backend: Optional[str] = None,
    logits_dtype=None,
    causal: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Backend-dispatched attention. See module docstring.

    ``causal`` masks ``j > i`` on the dense path (an iota comparison) and
    in the flash kernel (blocks above the diagonal are skipped); the
    single-pass ``fused`` kernel has no causal arm and refuses it.
    ``window`` (with ``causal``) narrows the mask to ``i - window < j <= i``
    on both paths; one no shorter than the sequence is the causal mask. In
    the flash kernel the shapes decide which kernels run the band
    (``flash_attention.band_form``): the resident pair, whose grid is the
    band (heads of whole lane tiles, equal blocks that divide the sequence,
    no bias, a cell that fits VMEM), else the causal kernels' arm, which
    skips the cells outside it; the dispatch log's ``band`` says which.

    ``logits_dtype`` sets the XLA path's softmax dtype (None = the
    deprecated process-wide default, f32 unless configured). The Pallas
    kernels always accumulate their softmax in f32 on-chip and ignore it.
    """
    requested = backend
    backend = backend or "auto"
    if backend not in ("auto", "xla", "pallas", "fused"):
        raise ValueError(f"unknown attention backend: {backend!r}")

    if window is not None:
        window = _flash.effective_window(window, causal, query.shape[-3])
    has_dropout = dropout_rate > 0.0 and not deterministic
    kernels_ok = (
        not has_dropout
        and query.ndim == 4  # [B, L, H, D] — the kernels' one layout
        and key.ndim == 4
        and (bias is None or bias.ndim == 4)
    )
    if kernels_ok:
        b, lq, h, d = query.shape
        dispatch = resolve_attention_backend(
            b, lq, key.shape[1], h, d,
            dtype=query.dtype, requested=requested, kernels_ok=True,
            causal=causal, value_dim=value.shape[-1], window=window,
        )
        backend = dispatch.backend
        cfg = dispatch.block_config or {}
        flash_blocks = {k: cfg[k] for k in ("block_q", "block_kv", "block_b") if k in cfg}
        flash_forms = None
        if backend == "pallas":
            lengths = (lq, key.shape[1], d, value.shape[-1])
            sizes = dict(batch_heads=b * h, itemsize=query.dtype.itemsize, **flash_blocks)
            blocks = {k: v for k, v in flash_blocks.items() if k != "block_b"}
            band = _flash.band_form(
                *lengths, heads=h, kv_heads=key.shape[2], window=window, biased=bias is not None,
                itemsize=query.dtype.itemsize, **blocks,
            )
            if band == "resident":  # operands where they lie, one call a direction
                flash_forms = {"layout": "in_place", "backward": "one_kernel"}
            else:
                flash_forms = {"layout": _flash.layout_form(*lengths, biased=bias is not None, **sizes)}
                if bias is None:
                    flash_forms["backward"] = _flash.backward_form(*lengths, **sizes)
            if key.shape[2] != h:
                by_layout = "index_maps" if flash_forms["layout"] == "in_place" else "repeated"
                flash_forms["grouped_kv"] = "in_cell" if band == "resident" else by_layout
            if band:
                flash_forms["band"] = band
                flash_forms.update(_flash.band_cells(lq, key.shape[1], window=window, form=band, **blocks))
        _log_dispatch(tuple(query.shape), key.shape[1], key.shape[2], requested, dispatch, flash_forms, window)
    else:
        if backend in ("pallas", "fused"):
            raise ValueError(
                f"{backend} attention backend requires 4-D [B, L, H, D] "
                "inputs and deterministic mode (attention dropout runs on "
                "the XLA path)"
            )
        backend, cfg, flash_blocks = "xla", {}, {}
    if backend == "fused":
        if causal or value.shape[-1] != query.shape[-1] or key.shape[-2] != query.shape[-2]:
            raise ValueError("the fused attention kernel has no causal arm, one head size and no grouped heads")
        # Shape ineligibility (kv_len over the single-block VMEM budget)
        # raises inside fused_attention with the budget numbers.
        kw = {k: cfg[k] for k in ("block_q", "block_b") if k in cfg}
        return _fused.fused_attention(query, key, value, bias, scale=scale, **kw)
    if backend == "pallas":
        return _flash.flash_attention(
            query, key, value, bias, scale=scale, causal=causal, window=window, **flash_blocks
        )
    return xla_attention(
        query,
        key,
        value,
        bias,
        scale=scale,
        dropout_rate=dropout_rate,
        dropout_rng=dropout_rng,
        deterministic=deterministic,
        logits_dtype=logits_dtype,
        causal=causal,
        window=window,
    )


def log_conv_form(fused: str, shape, width: int, dtype: str, form: dict) -> None:
    """The causal convolution's record (``ops/causal_conv.py::conv_form``) in
    the same log, one a traced shape and fused form: ``op``, the form
    (``silu`` | ``gated``), the ``[B, S, C]`` shape the taps run over, the
    taps, and the program: ``conv`` (``kernel`` | ``xla``) with its ``block_s``
    and ``block_c`` (and how it ``reads`` a projection laid out by key head),
    or with what ``refused`` the kernel. It stands at the file's end: the
    Mosaic calls' bodies carry this file's line numbers into a compiled step
    and into its cache key."""
    _log_once(("causal_conv", fused, shape, width, dtype, form["conv"]), {
        "op": "causal_conv", "fused": fused, "shape": list(shape), "width": width, "dtype": dtype, **form,
    })


def log_sum_form(shape, tokens: int, dtype: str, result: str, weighted: bool, form: dict) -> None:
    """The expert layer's sum of rows by token (``ops/rows_to_tokens.py::
    sum_form``) in the same log, one a traced shape and caller: ``op``, the
    ``[C, D]`` shape of the routed buffer, the ``tokens`` summed onto, the
    rows' ``dtype`` and the ``result``'s, whether a weight a row is multiplied
    in (``weighted``: ``moe/combine``'s forward; without, ``moe/dispatch``'s
    backward), and the program: ``sum`` (``kernel`` | ``xla``) with its
    ``tile`` of tokens and ``unit`` of rows a copy, or with what ``refused``
    the kernel."""
    _log_once(("rows_to_tokens", tuple(shape), tokens, dtype, result, weighted, form["sum"]), {
        "op": "rows_to_tokens", "shape": list(shape), "tokens": tokens, "dtype": dtype, "result": result,
        "weighted": weighted, **form,
    })
