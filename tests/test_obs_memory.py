"""Memory telemetry (sav_tpu/obs/memory.py): hbm_stats degrades to {}
on backends without memory_stats; RetraceCounter sees new jit traces."""

import jax
import jax.numpy as jnp

from sav_tpu.obs.memory import RetraceCounter, hbm_stats


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


def test_retrace_counter_counts_new_traces():
    @jax.jit
    def f(x):
        return x * 2

    f(jnp.ones((2,)))  # first trace
    counter = RetraceCounter(f)
    if not counter.active:  # running jax lacks _cache_size(): degrade path
        assert counter.delta() == 0
        return
    assert counter.delta() == 0  # same shape -> cache hit
    f(jnp.ones((2,)))
    assert counter.delta() == 0
    f(jnp.ones((3,)))  # new shape -> retrace
    assert counter.delta() == 1
    f(jnp.ones((4, 4)))
    f(jnp.ones((5, 5)))
    assert counter.delta() == 2
    assert counter.delta() == 0  # diffing, not cumulative


def test_retrace_counter_degrades_without_cache_size():
    counter = RetraceCounter(lambda x: x)  # plain function: no _cache_size
    assert not counter.active
    assert counter.delta() == 0
