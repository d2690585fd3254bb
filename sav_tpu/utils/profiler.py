"""Profiling & tracing.

The reference had no profiling at all (SURVEY.md §5 'Tracing / profiling').
This module provides the TPU-native equivalents:

- :func:`trace` — context manager around ``jax.profiler`` that writes an
  XPlane trace viewable in TensorBoard/Perfetto; the standard tool for
  finding input-bound vs compute-bound steps on TPU.
- :class:`StepTimer` — host-side throughput/latency tracker with jitter
  percentiles, for the images/sec counters the training loop logs.
- :func:`benchmark_fn` — microbenchmark harness for jitted functions and
  Pallas kernels (compile excluded, device-synced timing), used by the
  kernel cross-check/benchmark tests and ``bench.py``-style tooling.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Optional

import jax
import numpy as np


def start_trace(log_dir: str, *, host_tracer_level: int = 2) -> None:
    """``jax.profiler.start_trace`` with host-tracer options when the
    running jax supports them (single implementation for the context
    manager and the trainer's step-window profiling)."""
    options = None
    try:  # ProfileOptions is a recent jax addition; fall back silently.
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = host_tracer_level
    except Exception:
        options = None
    kwargs = {"profiler_options": options} if options is not None else {}
    try:
        jax.profiler.start_trace(log_dir, **kwargs)
    except TypeError:  # older signature without profiler_options
        jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: Optional[str], *, host_tracer_level: int = 2):
    """Capture a ``jax.profiler`` trace into ``log_dir``.

    No-op when ``log_dir`` is None so call sites can leave the hook wired
    unconditionally (``with trace(cfg.profile_dir): step()``).
    """
    if log_dir is None:
        yield
        return
    start_trace(log_dir, host_tracer_level=host_tracer_level)
    try:
        yield
    finally:
        stop_trace()


class StepTimer:
    """Rolling step-latency / throughput tracker.

    Host-side: call :meth:`tick` once per (logical) step after the device
    work for that step has been dispatched. Throughput uses wall time
    between ticks, which on a steady pipeline equals device step time.
    """

    def __init__(self, items_per_step: int = 0, window: int = 100):
        self.items_per_step = items_per_step
        self.window = window
        self._durations: list[float] = []
        self._last: Optional[float] = None

    def tick(self) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._durations.append(now - self._last)
            if len(self._durations) > self.window:
                self._durations.pop(0)
        self._last = now

    def reset(self) -> None:
        """Forget the last tick (call after eval/checkpoint pauses so the
        gap doesn't pollute the next interval)."""
        self._last = None

    @property
    def num_ticks(self) -> int:
        return len(self._durations)

    def summary(self) -> dict[str, float]:
        if not self._durations:
            return {}
        d = np.asarray(self._durations)
        out = {
            "step_time_mean_s": float(d.mean()),
            "step_time_p50_s": float(np.percentile(d, 50)),
            "step_time_p95_s": float(np.percentile(d, 95)),
        }
        if self.items_per_step:
            out["items_per_sec"] = self.items_per_step / float(d.mean())
        return out


def benchmark_fn(
    fn: Callable[..., Any],
    *args: Any,
    iters: int = 10,
    warmup: int = 2,
    **kwargs: Any,
) -> dict[str, float]:
    """Time a device computation: compile/warmup excluded, output-synced.

    Returns mean/min seconds per call. ``fn`` should return a jax array or
    pytree of arrays; synchronization is via ``block_until_ready`` on every
    leaf plus a final ``device_get`` (the host then holds a value only a
    finished execution can produce).
    """

    def sync(out):
        for leaf in jax.tree.leaves(out):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
        leaves = jax.tree.leaves(out)
        if leaves and hasattr(leaves[0], "addressable_shards"):
            jax.device_get(jax.tree.map(lambda x: x.ravel()[0], leaves[0]))
        return out

    for _ in range(max(warmup, 1)):
        sync(fn(*args, **kwargs))

    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sync(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    t = np.asarray(times)
    return {
        "mean_s": float(t.mean()),
        "min_s": float(t.min()),
        "p50_s": float(np.percentile(t, 50)),
        "iters": float(iters),
    }
