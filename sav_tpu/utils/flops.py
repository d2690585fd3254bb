"""FLOPs / MFU helpers shared by the trainer and bench.py.

XLA's ``compiled.cost_analysis()`` reports the **per-device** FLOPs of the
SPMD-partitioned executable (verified on an 8-way sharded program: exactly
1/8 of the single-device count). MFU is therefore computed per chip:

    mfu = per_device_flops / step_time / per_chip_peak

which is correct for any mesh size without knowing the global batch.
"""

from __future__ import annotations

import jax

# Peak dense bf16 FLOP/s per chip, keyed by the exact
# ``jax.Device.device_kind`` string, each with the source of the number.
# A device that is not in the table is an error, not a default: a wrong
# peak makes every MFU and roofline share wrong without a trace.
PEAK_FLOPS_PER_CHIP = {
    "TPU v4": (275e12, "Google Cloud documentation, TPU v4"),
    "TPU v5 lite": (197e12, "Google Cloud documentation, TPU v5e"),
    "TPU v5": (459e12, "Google Cloud documentation, TPU v5p"),
    "TPU v6 lite": (918e12, "Google Cloud documentation, TPU v6e"),
}


class UnknownDeviceKindError(LookupError):
    """``device_kind`` has no entry in :data:`PEAK_FLOPS_PER_CHIP`."""


def per_chip_peak_flops(devices=None) -> tuple[float, str]:
    """(peak bf16 FLOP/s of one chip, where the number comes from).

    Raises :class:`UnknownDeviceKindError` for a ``device_kind`` the table
    does not list — add the kind and its published peak to the table, or
    pass an explicit ``--peak-flops``.
    """
    devices = jax.devices() if devices is None else devices
    kind = getattr(devices[0], "device_kind", None)
    try:
        return PEAK_FLOPS_PER_CHIP[kind]
    except KeyError:
        raise UnknownDeviceKindError(
            f"no peak FLOP/s known for device_kind {kind!r} (known: "
            f"{sorted(PEAK_FLOPS_PER_CHIP)}); add it to "
            "sav_tpu/utils/flops.py::PEAK_FLOPS_PER_CHIP with its source "
            "or pass --peak-flops"
        ) from None


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` normalized to one dict ({} if absent).

    Backends disagree on shape: TPU returns a dict, CPU a one-element
    list of dicts — normalize so callers (``compiled_flops``,
    ``obs/costs.py``) read ``'flops'``/``'bytes accessed'`` uniformly.
    """
    try:
        cost = compiled.cost_analysis()
    except Exception:  # pragma: no cover - backend-dependent
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}


def compiled_flops(compiled) -> float:
    """Per-device FLOPs from a compiled executable (0.0 if unavailable)."""
    try:
        return float(xla_cost_analysis(compiled).get("flops", 0.0) or 0.0)
    except Exception:  # pragma: no cover - backend-dependent
        return 0.0
