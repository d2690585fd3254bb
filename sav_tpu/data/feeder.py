"""Async double-buffered device feeder.

The trainer's fit() loop was structurally serial: every step blocked on
``next(data_iter)`` and then on ``shard_batch`` (a synchronous
``device_put``) before the device step could even dispatch, so host fetch
and host→device transfer were pure addends on top of the ~100 ms device
step (PERF.md §7 — 2,388 img/s device-resident vs 93–169 img/s fed).
:class:`DeviceFeeder` pipelines the three stages instead:

    host fetch (batch N+2)  ──┐  background thread
    device_put (batch N+1)  ──┤  (bounded queue, depth knob)
    device step (batch N)   ──┘  training thread

A single background thread pulls host batches, immediately places them on
the mesh via the caller's ``place_fn`` (typically ``Trainer.shard_batch``
— per-leaf NamedShardings, multi-process assembly included), and pushes
the *placed* batches into a bounded queue. ``depth=2`` is classic double
buffering: at most ``depth`` placed batches wait on device beyond the one
in flight, so HBM exposure is bounded while transfer of batch N+1 hides
behind compute of step N. The queue's ``maxsize`` is the backpressure —
a slow consumer stalls the worker, never the other way around.

Semantics the trainer relies on (unit-tested in tests/test_feeder.py):

- **Drain**: the source iterator's ``StopIteration`` is delivered to the
  consumer exactly once, after every already-placed batch has been
  consumed; subsequent ``next()`` calls keep raising ``StopIteration``.
- **Exception propagation**: an exception in the source iterator or in
  ``place_fn`` is re-raised in the consumer thread (after the batches
  placed before it), not swallowed on the worker.
- **Shutdown**: ``close()`` (also via context manager) stops the worker
  promptly even when it is blocked on a full queue; it never joins a
  thread that is blocked inside the source iterator forever (the worker
  is a daemon and checks the stop flag between stages).

Telemetry: the feeder keeps worker-side counters (host fetch seconds,
device_put seconds, queue-depth high-water/occupancy) exposed by
:meth:`stats`; the trainer publishes them as ``feeder/*`` gauges on the
goodput ledger so a run's report shows the overlap working — in feeder
mode the ledger's ``input_wait`` is the consumer's residual queue wait
and ``h2d`` on the training thread is ~0, while ``feeder/h2d_s`` shows
where the placement time actually went (overlapped). The same two stages
are the spans ``sav:feeder/fetch`` and ``sav:feeder/place``
(sav_tpu/obs/spans.py): a profiler session shows the worker's thread
beside the device's operations.

Stdlib + the injected ``place_fn`` only — no jax import at module level,
so the data layer stays importable in TF-free/device-free contexts.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

from sav_tpu.obs.spans import SpanTracer


class DeviceFeeder:
    """Bounded async pipeline: host iterator → place_fn → placed-batch queue.

    Args:
      iterator: host batch source (dicts of numpy arrays, typically).
      place_fn: called on the worker thread with each host batch; returns
        the placed (device) batch the consumer receives. Pass
        ``Trainer.shard_batch`` for SPMD-correct per-leaf placement.
      depth: max placed batches queued beyond the one the consumer holds
        (2 = double buffering). Also the backpressure bound.
      name: thread-name suffix for stack dumps (the obs watchdog prints
        every thread; a recognizable name keeps its reports readable).
      tracer: the caller's span tracer, so that the worker's spans land in
        its Chrome file too; without one they reach the profiler only.
    """

    _POLL_S = 0.1  # stop-flag responsiveness for blocking queue ops

    def __init__(
        self,
        iterator: Iterator[dict],
        place_fn: Callable[[dict], Any],
        *,
        depth: int = 2,
        name: str = "device-feeder",
        tracer: Optional[SpanTracer] = None,
    ):
        if depth < 1:
            raise ValueError(f"feeder depth must be >= 1, got {depth}")
        self.depth = depth
        self._iterator = iterator
        self._place_fn = place_fn
        self._tracer = tracer if tracer is not None else SpanTracer(None)
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._done = object()
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._finished = False
        # Worker-side counters. Python attribute writes are atomic under
        # the GIL; the consumer only ever reads them for telemetry.
        self._fetch_s = 0.0
        self._put_s = 0.0
        self._batches = 0
        self._depth_max = 0
        self._depth_sum = 0
        self._wait_s = 0.0  # consumer-side blocked time
        self._thread = threading.Thread(
            target=self._worker, name=name, daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------- worker

    def _enqueue(self, item) -> bool:
        """Bounded put that stays responsive to close(); True if queued."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=self._POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            while not self._stop.is_set():
                with self._tracer.span("feeder/fetch") as span:
                    try:
                        batch = next(self._iterator)
                    except StopIteration:
                        break
                self._fetch_s += span.seconds
                with self._tracer.span("feeder/place") as span:
                    placed = self._place_fn(batch)
                self._put_s += span.seconds
                self._batches += 1
                if not self._enqueue(placed):
                    return  # closed while blocked on a full queue
                d = self._queue.qsize()
                self._depth_sum += d
                self._depth_max = max(self._depth_max, d)
        except BaseException as e:  # re-raised on the consumer thread
            self._err = e
        finally:
            self._enqueue(self._done)

    # ----------------------------------------------------------- consumer

    def __iter__(self):
        return self

    def __next__(self):
        # Terminal states persist: the sentinel is consumed exactly once,
        # so later next() calls must not block on an empty queue.
        if self._finished:
            if self._err is not None:
                raise self._err
            raise StopIteration
        # Timed get re-checking the stop flag (mirror of _enqueue): after
        # close() the worker drops everything including the sentinel, so
        # an untimed get from a consumer on another thread would block
        # forever instead of seeing the closed state.
        t0 = time.perf_counter()
        while True:
            if self._stop.is_set():
                self._wait_s += time.perf_counter() - t0
                raise RuntimeError("DeviceFeeder is closed")
            try:
                item = self._queue.get(timeout=self._POLL_S)
                break
            except queue.Empty:
                continue
        self._wait_s += time.perf_counter() - t0
        if item is self._done:
            self._finished = True
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self) -> None:
        """Stop the worker and release the queue. Idempotent.

        Safe to call with the worker in any state (blocked on a full
        queue, mid-place, already drained). Does not wait on the source
        iterator: a worker blocked inside ``next(iterator)`` is a daemon
        thread and dies with the process; everything it might still
        enqueue after close() is dropped by the poisoned stop flag.
        """
        self._stop.set()
        # Unblock a worker stuck in queue.put by draining; bounded loop —
        # the worker checks the stop flag at least every _POLL_S.
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5 * self._POLL_S)
        # The drain races the worker's in-flight put: the slot it freed can
        # be re-filled just after get_nowait saw Empty. The worker never
        # *starts* a put once the flag is set, so after the join one more
        # drain releases anything that slipped in — without it a placed
        # device batch could stay referenced by the dead queue.
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ---------------------------------------------------------- telemetry

    def stats(self) -> dict:
        """Worker/consumer counters for the goodput ledger's gauges.

        ``h2d_s``/``fetch_s`` are background-thread seconds (overlapped
        with device compute, NOT training-thread wall time); ``wait_s``
        is the consumer's blocked time (what the trainer also books as
        ``input_wait``); ``depth_avg``/``depth_max`` show whether the
        buffer actually stayed full (a starved feeder sits at 0).
        """
        batches = self._batches
        return {
            "batches": float(batches),
            "fetch_s": round(self._fetch_s, 6),
            "h2d_s": round(self._put_s, 6),
            "wait_s": round(self._wait_s, 6),
            "depth": float(self.depth),
            "depth_max": float(self._depth_max),
            "depth_avg": round(self._depth_sum / batches, 4) if batches else 0.0,
        }
