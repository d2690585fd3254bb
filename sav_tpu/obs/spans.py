"""The program's own timeline: host spans where the device trace is.

One span API for ``Trainer.fit``, ``DeviceFeeder`` and start-up. A span
named ``fit/dispatch`` is emitted three ways from one ``with``:

- as a ``jax.profiler.TraceAnnotation`` called ``sav:fit/dispatch``. While
  any profiler session runs (``TrainConfig.profile_dir``'s window,
  autoprof, the benchmark's ``--trace 1``) it lands in the trace's
  ``/host:CPU`` plane beside the device's ``XLA Ops``, on the profiler's
  clock, from whichever thread emitted it. With no session the annotation
  is a flag test and records nothing: nothing turns spans on or off.
- as a complete event of the Chrome file (``spans.trace.json``, Trace
  Event Format, loadable in Perfetto and ``chrome://tracing``) when the
  tracer was given a path (``TrainConfig.trace_spans``);
- as seconds on a goodput ledger's bucket, where the span names one.

*Phase* spans (``Trainer.__init__``, ``init_state``, ``fit``'s compile)
also enter the process timeline: a bounded, always-on list of finished
spans on ``time.perf_counter``, for what happens before any profiler can
run. :func:`timeline` reads it. Per-step spans never enter it. The lazy
imports of ``sav_tpu/_lazy.py`` enter it through :func:`record_phase`,
timed there with a bare clock pair. While a phase span is open its name is
on its thread's stack (:func:`open_phase`): the compile log
(``obs/compile_log.py``) names it as the cause of what jax compiles
meanwhile. Per-step spans never touch the stack either.

Stdlib-only at import: the supervisor and the serve pool import this
package without ``jax``. The annotation class is looked up only once
``jax`` is in ``sys.modules``: a process without jax has no profiler
session to write to.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

PREFIX = "sav:"
TIMELINE_MAX = 256

_timeline: deque = deque(maxlen=TIMELINE_MAX)
_timeline_lock = threading.Lock()
_open = threading.local()
_annotation_cls = None


def timeline() -> list[tuple[str, float, float]]:
    """The process's finished phase spans, oldest first, as ``(name,
    start, end)`` on ``time.perf_counter``; the newest ``TIMELINE_MAX``."""
    with _timeline_lock:
        return list(_timeline)


def _keep(name: str, start: float, end: float) -> None:
    with _timeline_lock:
        _timeline.append((name, start, end))


def record_phase(name: str, start: float, end: float) -> None:
    """Enter a finished phase span, timed by the caller on
    ``time.perf_counter``, in the process timeline as ``sav:<name>``."""
    _keep(PREFIX + name, start, end)


def _push(name: str) -> None:
    try:
        _open.stack.append(name)
    except AttributeError:
        _open.stack = [name]


def push_open(name: str) -> None:
    """``sav:<name>`` is open on this thread from now until :func:`pop_open`:
    for a phase that is timed by its caller and entered by
    :func:`record_phase`."""
    _push(PREFIX + name)


def pop_open() -> None:
    _open.stack.pop()


def open_phase() -> Optional[str]:
    """The innermost phase span open on this thread, None where none is."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


def _annotation(name: str):
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls(name)


class _Span:
    __slots__ = ("tracer", "name", "bucket", "in_timeline", "args", "start", "seconds", "annotation")

    def __init__(self, tracer, name, bucket, in_timeline, args):
        self.tracer, self.name, self.bucket = tracer, PREFIX + name, bucket
        self.in_timeline, self.args = in_timeline, args

    def __enter__(self):
        self.annotation = _annotation(self.name)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self.seconds = end - self.start
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        if self.in_timeline:
            _keep(self.name, self.start, end)
        tracer = self.tracer
        if tracer is None:
            return False
        if self.bucket is not None:
            tracer.ledger.account(self.bucket, self.seconds)
        if tracer.enabled:
            tracer._append(
                {"name": self.name, "ph": "X", "ts": self.start * 1e6, "dur": self.seconds * 1e6},
                self.args,
            )
        return False


class _Phase(_Span):
    """A span of the process timeline: open on its thread's stack while it
    runs. A few dozen a run; the loop's spans stay plain ``_Span``s."""

    __slots__ = ()

    def __enter__(self):
        _push(self.name)
        return super().__enter__()

    def __exit__(self, *exc):
        try:
            return super().__exit__(*exc)
        finally:
            pop_open()


def phase(name: str) -> _Span:
    """A phase span with no tracer behind it (start-up, constructors): the
    annotation and the timeline only."""
    return _Phase(None, name, None, True, None)


def in_phase(name: str):
    """Decorator: every call of the function is the phase span ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)

        return wrapped

    return wrap


class SpanTracer:
    """Emits spans; collects the Chrome file's events where it has a path.

    ``path=None`` writes no file: spans still reach the profiler and the
    ledger, so call sites wire one tracer unconditionally. Thread-safe:
    the feeder, watchdog and checkpoint threads emit beside the train loop.
    Timestamps are ``time.perf_counter`` microseconds, the clock of the
    process timeline.
    """

    def __init__(self, path: Optional[str], *, ledger=None, process_name: str = "sav_tpu"):
        self.path = path
        self.enabled = path is not None
        self.ledger = ledger
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._made = time.perf_counter()
        if self.enabled:
            # Metadata event names the process row in the Perfetto UI.
            self._events.append({
                "name": "process_name", "ph": "M", "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": {"name": process_name},
            })

    def span(self, name: str, *, bucket: Optional[str] = None, in_timeline: bool = False,
             **args) -> _Span:
        """``with tracer.span("fit/dispatch", step=3):`` emits
        ``sav:fit/dispatch``. ``bucket`` books the span's seconds on the
        tracer's ledger; ``in_timeline`` marks a phase span, kept in the
        process timeline; ``args`` go to the Chrome event."""
        if bucket is not None and self.ledger is None:
            raise ValueError(f"span {name!r} names bucket {bucket!r} but the tracer has no ledger")
        return (_Phase if in_timeline else _Span)(self, name, bucket, in_timeline, args)

    def _append(self, event: dict, args) -> None:
        event.update(pid=os.getpid(), tid=threading.get_ident())
        if args:
            event["args"] = args
        with self._lock:
            self._events.append(event)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker of the Chrome file (stall anomalies,
        incidents...)."""
        if not self.enabled:
            return
        self._append(
            {"name": PREFIX + name, "ph": "i", "ts": time.perf_counter() * 1e6, "s": "t"}, args
        )

    @property
    def num_events(self) -> int:
        with self._lock:
            return len(self._events)

    def write(self) -> Optional[str]:
        """Write the trace file (returns its path; None when disabled).

        Safe to call repeatedly — crash-prone loops can flush
        periodically and the final file wins.
        """
        if not self.enabled:
            return None
        with self._lock:
            events = list(self._events)
        # What jax traced, lowered and compiled since this tracer was made,
        # by name beside the spans, on their clock.
        from sav_tpu.obs import compile_log

        pid = os.getpid()
        for record in compile_log.log(since=self._made):
            args = {k: record[k] for k in ("cause", "cache") if k in record}
            events.append({
                "name": f"{PREFIX}compile/{record['kind']}:{record['fun_name']}", "ph": "X",
                "ts": record["start"] * 1e6, "dur": (record["end"] - record["start"]) * 1e6,
                "pid": pid, "tid": record["thread"], "args": args,
            })
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(self.path, "w") as f:
            json.dump(
                {"traceEvents": events, "displayTimeUnit": "ms"},
                f,
            )
        return self.path
