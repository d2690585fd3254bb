"""Device-memory telemetry.

**HBM creep** — fragmentation or a leaked donation growing bytes-in-use
until a late-run OOM — is the silent failure this module makes visible.
:func:`hbm_stats` samples ``device.memory_stats()`` (a PJRT API: present
on TPU, absent or empty on CPU — degrade to ``{}``, never raise) and the
trainer folds the numbers into its logged metrics.
"""

from __future__ import annotations


def hbm_stats(devices=None) -> dict[str, float]:
    """Aggregate ``memory_stats()`` over local devices; ``{}`` when the
    backend has none (CPU) or refuses the query.

    Keys: ``hbm_bytes_in_use`` (sum), ``hbm_peak_bytes`` (max over
    devices — the OOM-relevant number on a symmetric mesh), and
    ``hbm_bytes_limit`` (sum) when the backend reports it.
    """
    import jax

    devices = jax.local_devices() if devices is None else devices
    in_use = peak = limit = 0.0
    seen = False
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:
            continue
        if not stats:
            continue
        seen = True
        in_use += float(stats.get("bytes_in_use", 0))
        peak = max(peak, float(stats.get("peak_bytes_in_use", 0)))
        limit += float(stats.get("bytes_limit", 0))
    if not seen:
        return {}
    out = {"hbm_bytes_in_use": in_use, "hbm_peak_bytes": peak}
    if limit:
        out["hbm_bytes_limit"] = limit
    return out
