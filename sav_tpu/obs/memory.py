"""Device-memory telemetry and retrace detection.

Two silent failure modes this module makes visible:

- **HBM creep** — fragmentation or a leaked donation growing
  bytes-in-use until a late-run OOM. :func:`hbm_stats` samples
  ``device.memory_stats()`` (a PJRT API: present on TPU, absent or empty
  on CPU — degrade to ``{}``, never raise) and the trainer folds the
  numbers into its logged metrics.
- **Silent recompilation** — a leaked weak type or shape-polymorphic
  batch makes ``jit`` re-trace every step; each retrace costs seconds to
  minutes, and nothing in the metrics says why the run got slow.
  :class:`RetraceCounter` diffs a jitted function's compile-cache size
  between logging windows, so a nonzero ``retraces`` metric after warmup
  is an immediate red flag.
"""

from __future__ import annotations

from typing import Optional


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


class RetraceCounter:
    """Counts new traces of a ``jax.jit`` function between checks.

    Uses the private-but-stable ``_cache_size()`` accessor; when the
    running jax lacks it the counter degrades to always-zero (``active``
    is False) rather than failing — telemetry must never take a run down.
    """

    def __init__(self, fn):
        self._fn = fn
        self._last = self._size()

    def _size(self) -> Optional[int]:
        try:
            return int(self._fn._cache_size())
        except Exception:
            return None

    @property
    def active(self) -> bool:
        return self._size() is not None

    def delta(self) -> int:
        """New traces since the previous ``delta()`` (or construction).

        The first trace of a fresh function is expected compilation, not a
        *re*-trace, so callers typically take one ``delta()`` after
        warmup and treat any later nonzero as an anomaly.
        """
        size = self._size()
        if size is None:
            return 0
        new = max(size - (self._last or 0), 0)
        self._last = size
        return new
