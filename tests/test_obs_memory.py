"""Memory telemetry (sav_tpu/obs/memory.py): hbm_stats degrades to {}
on backends without memory_stats."""

from sav_tpu.obs.memory import hbm_stats


def test_hbm_stats_never_raises_on_cpu():
    stats = hbm_stats()
    assert isinstance(stats, dict)
    # CPU backends either report nothing ({}) or real byte counts.
    for v in stats.values():
        assert v >= 0


def test_hbm_stats_aggregates_fake_devices():
    class Dev:
        def __init__(self, in_use, peak, limit=0):
            self._s = {
                "bytes_in_use": in_use, "peak_bytes_in_use": peak,
                **({"bytes_limit": limit} if limit else {}),
            }

        def memory_stats(self):
            return self._s

    stats = hbm_stats([Dev(100, 150, 1000), Dev(200, 120, 1000)])
    assert stats["hbm_bytes_in_use"] == 300
    assert stats["hbm_peak_bytes"] == 150  # max, not sum: the OOM number
    assert stats["hbm_bytes_limit"] == 2000


def test_hbm_stats_skips_raising_devices():
    class Bad:
        def memory_stats(self):
            raise RuntimeError("backend refused")

    assert hbm_stats([Bad()]) == {}
