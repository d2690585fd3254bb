"""Per-step compute cost model — where do the FLOPs go, and how close to
the roofline is the run.

The ROADMAP's "fast as the hardware allows" north star is unfalsifiable
without an achieved-vs-peak number, so this module turns a train step into
a FLOPs/bytes estimate two ways (the MFU accounting popularized by PaLM,
Chowdhery et al. 2022, and the scaling-efficiency methodology of
Megatron-LM, Shoeybi et al. 2019):

- **XLA cost analysis** — ``jit(step).lower(...).compile().cost_analysis()``
  reports the *per-device* FLOPs of the partitioned executable
  (:mod:`sav_tpu.utils.flops`). Exact for whatever XLA actually emitted,
  but a single opaque total.
- **Analytic fallback** — a per-layer-group walk of the parameter tree
  (matmul kernels cost ``2 * tokens * prod(shape)``; attention adds the
  parameter-free QK^T / AV einsums, ``4 * B * L^2 * H * Dh`` per block)
  keyed off the same top-level group naming
  :func:`sav_tpu.obs.diagnostics._group_of` uses. Approximate (it ignores
  norms/bias/softmax flops, a few percent on ViT shapes), but it exists
  on any backend and — unlike the XLA total — it decomposes, so it is
  also the *attribution* source even when the total comes from XLA.

MFU is per chip: ``per_device_flops / step_time / per_chip_peak``. The
peak table lives in :data:`sav_tpu.utils.flops.PEAK_FLOPS_PER_CHIP`;
:func:`resolve_peak_flops` adds an explicit override (``--peak-flops``)
and a deterministic fake peak for CPU so the whole MFU/attribution
pipeline is assertable in tier-1 without an accelerator (the fake is
labeled ``cpu-fake`` everywhere it surfaces — never compare it to the
hardware baseline).

Training-step FLOPs use the standard forward + backward ≈ 3x forward
multiplier (the backward pass does ~2x the forward matmul work); gradient
accumulation does not change the total (same images per optimizer step).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from sav_tpu.obs.diagnostics import _group_of
from sav_tpu.utils.device_check import cpu_requested
from sav_tpu.utils.flops import per_chip_peak_flops, xla_cost_analysis

# Deterministic stand-in peak for CPU runs: obviously fake (no CPU does
# 1 TFLOP/s dense f32 on one core), but stable across hosts so tier-1
# can assert the MFU plumbing end-to-end. Labeled 'cpu-fake' wherever it
# is used.
CPU_FAKE_PEAK_FLOPS = 1.0e12

# Forward+backward multiplier over forward matmul FLOPs.
TRAIN_STEP_MULTIPLIER = 3.0

# ---- dot-dtype axis (ISSUE 17). The cost model's traffic and roofline
# numbers are dtype-dependent once the int8 arm exists: an int8 dot
# moves 1 byte/element where bf16 moves 2, and the MXU's int8 pipe peaks
# at 2x its bf16 FLOP/s (the TPU generations the peak table knows all
# share the 2:1 int8:bf16 ratio; the same convention the AQT paper's
# speedups are quoted against). ``None`` keys mean "whatever the compute
# dtype was" — the pre-quant behavior, so existing callers are unchanged.
DOT_DTYPE_BYTES = {"f32": 4, "float32": 4, "bf16": 2, "bfloat16": 2, "int8": 1}

# Peak-FLOP/s multiplier over the table's (bf16) number, per dot dtype.
DOT_DTYPE_PEAK_FACTOR = {"bf16": 1.0, "bfloat16": 1.0, "f32": 1.0,
                         "float32": 1.0, "int8": 2.0}


def dot_dtype_bytes(dot_dtype: Optional[str], default: int = 2) -> int:
    """Bytes per element moved by a dot of the named dtype (``None`` =
    ``default``, the caller's compute-dtype width)."""
    if dot_dtype is None:
        return default
    return DOT_DTYPE_BYTES.get(str(dot_dtype).lower(), default)

# Attribution component names (the gauge/manifest vocabulary). The
# analytic walk buckets every parameter into one of these; QK/AV is the
# parameter-free attention einsum pair, ATTN_PROJ the qkv/out projections.
COMP_PATCH_EMBED = "patch_embed"
COMP_ATTN_PROJ = "attention_proj"
COMP_ATTN_QKAV = "attention_qkav"
COMP_FFN = "ffn"
COMP_HEAD = "head"
COMP_OTHER = "other"

_ATTN_MARKERS = (
    "attention", "attn", "to_qkv", "to_out", "to_q", "to_kv",
    "query", "key", "value",
)
_FFN_MARKERS = ("ffblock", "feedforward", "mlp", "fc1", "fc2", "moeff")
_PATCH_MARKERS = ("patchembed", "patch_embed", "stem", "conv_stem")
_QKV_KERNEL_MARKERS = ("to_qkv", "to_q", "query")


def resolve_peak_flops(
    override: Optional[float] = None,
    devices=None,
    *,
    dot_dtype: Optional[str] = None,
) -> tuple[float, str]:
    """Per-chip peak FLOP/s and where the number came from.

    Resolution order: explicit ``override`` (``--peak-flops`` /
    ``TrainConfig.peak_flops``) → on the CPU, when the CPU was asked for
    (``JAX_PLATFORMS=cpu`` and its equivalents —
    :func:`sav_tpu.utils.device_check.cpu_requested`), the deterministic
    fake, labeled ``cpu-fake`` → the device-kind table
    (:data:`~sav_tpu.utils.flops.PEAK_FLOPS_PER_CHIP`, exact match on
    ``device_kind``). An accelerator the table does not know raises
    :class:`~sav_tpu.utils.flops.UnknownDeviceKindError`, and so does a
    CPU nobody asked for: a made-up peak must never stand in for a
    device's.

    ``dot_dtype`` keys the peak by what the dots actually run in
    (:data:`DOT_DTYPE_PEAK_FACTOR` — ``"int8"`` doubles the table's bf16
    number, the MXU's 2:1 int8:bf16 ratio; the source string carries the
    scaling so an int8-scaled peak is never mistaken for the table's).
    An explicit ``override`` is taken verbatim — the operator stated the
    peak for the arm they are measuring.
    """
    if override:
        return float(override), "override"
    import jax

    factor = DOT_DTYPE_PEAK_FACTOR.get(
        str(dot_dtype).lower() if dot_dtype is not None else "bf16", 1.0
    )
    tag = f":{str(dot_dtype).lower()}" if factor != 1.0 else ""
    devices = jax.devices() if devices is None else devices
    if getattr(devices[0], "platform", None) == "cpu" and cpu_requested():
        return CPU_FAKE_PEAK_FLOPS * factor, "cpu-fake" + tag
    peak, _source = per_chip_peak_flops(devices)
    return peak * factor, "device-table" + tag


@dataclasses.dataclass
class StepCost:
    """One training step's compute cost, per device.

    ``flops``/``bytes_accessed`` are per-device (matching XLA's
    ``cost_analysis`` convention — the batch shards over devices);
    ``attribution`` maps component → fraction of the *analytic* total
    (sums to ~1.0) and is always analytic, because the XLA total does
    not decompose; ``groups`` is the same attribution keyed by the
    top-level parameter-tree groups diagnostics uses
    (``grad_norm/<group>``), so the two telemetry families line up.
    """

    flops: float
    bytes_accessed: Optional[float]
    source: str  # 'xla-cost-analysis' | 'analytic'
    attribution: dict[str, float]
    groups: dict[str, float]
    num_tokens: int
    per_device_batch: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _leaf_info(path, leaf) -> tuple[str, str, tuple, int]:
    """(joined lowercase path, top group, shape, itemsize) of a param leaf.

    Works on concrete arrays and ``ShapeDtypeStruct``s alike, so the cost
    model can run on ``jax.eval_shape`` output without materializing
    parameters.
    """
    names = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
    joined = "/".join(names).lower()
    try:
        itemsize = np.dtype(leaf.dtype).itemsize
    except Exception:
        itemsize = 4
    return joined, _group_of(path), tuple(leaf.shape), itemsize


def infer_num_tokens(params: Any, image_size: int) -> int:
    """Sequence length of the encoder trunk, estimated from the params.

    Preference order: a learned ``pos_embed`` table ``(1, L, D)`` states L
    outright; else the patch-embed conv kernel ``(ph, pw, C, D)`` gives
    the patch grid (+1 when a top-level ``cls`` token exists); else assume
    the ViT-default 16px patch. An estimate — rotary/sincos models without
    a patch stem fall through to the default.
    """
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    has_cls = any(
        "cls" in _leaf_info(p, l)[0].split("/")[0] for p, l in leaves
    )
    for path, leaf in leaves:
        joined, _, shape, _ = _leaf_info(path, leaf)
        if "pos_embed" in joined and len(shape) == 3 and shape[0] == 1:
            return int(shape[1])
    for path, leaf in leaves:
        joined, group, shape, _ = _leaf_info(path, leaf)
        if len(shape) == 4 and any(
            m in group.lower() for m in _PATCH_MARKERS
        ):
            ph, pw = int(shape[0]), int(shape[1])
            if ph > 0 and pw > 0:
                grid = max(image_size // ph, 1) * max(image_size // pw, 1)
                return grid + (1 if has_cls else 0)
    return max(image_size // 16, 1) ** 2 + 1


def param_group_bytes(params: Any) -> dict[str, float]:
    """Shape-derived parameter bytes per layer group (+ ``_total``).

    The predicted side of memory forensics (obs/memdump.py): the live
    ``params``-class buffer total should match this; a gap is a
    param-shaped buffer the state no longer owns (donation leak) or a
    dtype drift. Groups are diagnostics' ``_group_of`` naming — the same
    keys as :class:`StepCost.groups` and ``grad_norm/<group>``.
    """
    import jax

    out: dict[str, float] = {}
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        _, group, shape, itemsize = _leaf_info(path, leaf)
        nbytes = float(np.prod(shape)) * itemsize if shape else float(itemsize)
        out[group] = out.get(group, 0.0) + nbytes
        total += nbytes
    out = dict(sorted(out.items()))
    out["_total"] = total
    return out


def _component_of(joined: str, group: str, shape: tuple) -> str:
    top = group.lower()
    if top == "head" or top.startswith("head"):
        return COMP_HEAD
    if any(m in top for m in _PATCH_MARKERS) or (
        len(shape) == 4 and "embed" in top
    ):
        return COMP_PATCH_EMBED
    if any(m in joined for m in _ATTN_MARKERS):
        return COMP_ATTN_PROJ
    if any(m in joined for m in _FFN_MARKERS):
        return COMP_FFN
    return COMP_OTHER


def analytic_train_step_cost(
    params: Any,
    *,
    batch_size: int,
    image_size: int,
    n_devices: int = 1,
    training: bool = True,
) -> StepCost:
    """Analytic per-device FLOPs/bytes for one train step over ``params``.

    ``batch_size`` is the *global* batch; the result is divided by
    ``n_devices`` to match ``cost_analysis``'s per-device convention.
    """
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    num_tokens = infer_num_tokens(params, image_size)
    b = float(batch_size)
    by_comp: dict[str, float] = {}
    by_group: dict[str, float] = {}
    param_bytes = 0.0
    attn_seen: set[str] = set()
    for path, leaf in leaves:
        joined, group, shape, itemsize = _leaf_info(path, leaf)
        size = float(np.prod(shape)) if shape else 1.0
        param_bytes += size * itemsize
        comp = _component_of(joined, group, shape)
        if len(shape) >= 2 and shape[0] != 1:
            # Matmul kernel: 2 * tokens * prod(shape) forward FLOPs
            # (leading-dim-1 leaves are broadcast tables — cls token,
            # pos_embed — added, not contracted: skipped). The
            # head sees one pooled token per image; everything else sees
            # the full trunk sequence (patch embed included: each of the
            # L patches is one (ph*pw*C -> D) matmul, and prod(shape)
            # already equals that inner product).
            tokens = b if comp == COMP_HEAD else b * num_tokens
            flops = 2.0 * tokens * size
            by_comp[comp] = by_comp.get(comp, 0.0) + flops
            by_group[group] = by_group.get(group, 0.0) + flops
        if any(m in joined for m in _QKV_KERNEL_MARKERS) and len(shape) >= 2:
            # One attention core per qkv/query kernel: the parameter-free
            # QK^T and AV einsums cost 2 * B * L^2 * (H * Dh) each. The
            # model width H*Dh is the kernel's trailing head dims (the
            # fused (D, 3, H, Dh) layout and a separate (D, H, Dh) query
            # kernel both end in H, Dh).
            module = joined.rsplit("/", 1)[0]
            if module not in attn_seen:
                attn_seen.add(module)
                hd = float(shape[-1]) * (
                    float(shape[-2]) if len(shape) >= 3 else 1.0
                )
                qkav = 4.0 * b * float(num_tokens) ** 2 * hd
                by_comp[COMP_ATTN_QKAV] = (
                    by_comp.get(COMP_ATTN_QKAV, 0.0) + qkav
                )
                by_group[group] = by_group.get(group, 0.0) + qkav
    mult = TRAIN_STEP_MULTIPLIER if training else 1.0
    total = sum(by_comp.values()) * mult
    n = max(int(n_devices), 1)
    attribution = {
        k: (v / (total / mult) if total else 0.0)
        for k, v in sorted(by_comp.items())
    }
    groups = {
        k: (v / (total / mult) if total else 0.0)
        for k, v in sorted(by_group.items())
    }
    # Rough traffic floor: the step reads params (fwd + bwd) and writes
    # updates (~3x param bytes) and reads the input batch once. A floor,
    # not a roofline denominator — activations are excluded on purpose.
    batch_bytes = b * image_size * image_size * 3 * 4 / n
    bytes_accessed = 3.0 * param_bytes + batch_bytes
    return StepCost(
        flops=total / n,
        bytes_accessed=bytes_accessed,
        source="analytic",
        attribution=attribution,
        groups=groups,
        num_tokens=num_tokens,
        per_device_batch=b / n,
    )


def train_step_cost(
    params: Any,
    *,
    batch_size: int,
    image_size: int,
    compiled=None,
    n_devices: int = 1,
    training: bool = True,
) -> StepCost:
    """The production cost estimate: XLA totals when a compiled executable
    is at hand, the analytic walk otherwise — attribution fractions come
    from the analytic model either way (XLA's total does not decompose).
    """
    cost = analytic_train_step_cost(
        params,
        batch_size=batch_size,
        image_size=image_size,
        n_devices=n_devices,
        training=training,
    )
    if compiled is not None:
        analysis = xla_cost_analysis(compiled)
        flops = float(analysis.get("flops", 0.0) or 0.0)
        if flops > 0:
            cost = dataclasses.replace(
                cost,
                flops=flops,
                bytes_accessed=float(
                    analysis.get("bytes accessed", 0.0) or 0.0
                ) or cost.bytes_accessed,
                source="xla-cost-analysis",
            )
    return cost


def publish_cost_gauges(
    ledger,
    cost: StepCost,
    *,
    peak_flops: Optional[float] = None,
    peak_source: str = "unknown",
) -> None:
    """Fold a :class:`StepCost` into a goodput ledger as gauges.

    Gauge vocabulary (flat_metrics prefixes these with ``goodput/``):
    ``flops/step_per_device``, ``flops/<component>_frac`` (the per-group
    attribution), and ``peak_flops`` when known. The achieved-rate pair
    (``flops_per_s``, ``mfu``) is published separately by the caller once
    step timings exist — see :func:`publish_mfu_gauges`.
    """
    ledger.set_gauge("flops/step_per_device", cost.flops)
    for comp, frac in cost.attribution.items():
        ledger.set_gauge(f"flops/{comp}_frac", frac)
    if peak_flops:
        ledger.set_gauge("peak_flops", peak_flops)
        ledger.set_gauge("peak_flops_is_fake", float(peak_source == "cpu-fake"))


def publish_mfu_gauges(
    ledger,
    *,
    step_flops: float,
    peak_flops: Optional[float],
    steps: int,
    step_seconds: float,
) -> Optional[float]:
    """Publish ``flops_per_s`` + ``mfu`` gauges from aggregate step time.

    Returns the MFU (or None when unreportable). ``step_seconds`` is the
    ledger's ``step`` bucket — training-thread wall attributed to device
    compute, the honest denominator for end-of-run utilization.
    """
    if not step_flops or steps <= 0 or step_seconds <= 0:
        return None
    flops_per_s = step_flops * steps / step_seconds
    ledger.set_gauge("flops_per_s", flops_per_s)
    if not peak_flops:
        return None
    mfu = flops_per_s / peak_flops
    ledger.set_gauge("mfu", mfu)
    return mfu
