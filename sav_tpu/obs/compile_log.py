"""The process's compile log: what jax traced, lowered and compiled, when,
from which cache state, and which phase asked for it.

jax reports every trace, lowering and backend compile through
``jax.monitoring``, as it starts and as it finishes. :func:`listen`
registers the log's listeners (``Trainer.__init__`` and
``ServeEngine.__init__`` call it where they place the persistent cache,
before their first compile), and each finished event becomes one record::

    {"kind": "trace" | "lower" | "backend", "fun_name": jax's own,
     "start": ..., "end": ...,           # time.perf_counter, the timeline's clock
     "thread": threading.get_ident(),
     "cause": "sav:fit/compile" | ... | None,
     # a backend record also:
     "cache": "hit" | "miss" | "off", "retrieval_s": ...,
     # a trace or lower record also: "nested": n}

``end`` is read in the callback and ``start = end - duration``: no
conversion from jax's ``time.time()``. ``cause`` is the innermost phase
span open on the emitting thread (``obs/spans.py::open_phase``) when the
event closed: ``None`` for a compile no phase asked for (an eager op, a
recompile inside the loop). ``cache`` comes from the persistent cache's own
events on that thread since its previous backend record: ``off`` where the
cache was not asked or has no directory, ``hit`` where it answered
(``retrieval_s`` is jax's time for the read), ``miss`` where it was asked
and the backend compiled.

A whole program's trace holds thousands of nested pjit traces (``matmul``,
``tanh``: 900 in one ``init_state`` of DeiT-S, more than the log's bound in
one expert-layer step), each an event inside the outer one's. jax also
reports when an event starts, so the log knows which events are open on a
thread: a trace that closes inside another open trace or lowering makes no
record and is counted in that one's ``nested``. A lowering or a backend
compile inside a trace (an eager op's while tracing) is a record, and
:func:`summary` counts every instant once, for the innermost record that
covers it.

Bounded: the newest ``LOG_MAX`` records, with a count of those dropped. Its
own store, so that the timeline's 256 phase spans outlive any number of
compiles. Stdlib-only at import; ``jax`` is imported in :func:`listen`.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

from sav_tpu.obs import spans

LOG_MAX = 4096
#: jax's own floor for what is worth a cache entry
#: (``jax_persistent_cache_min_compile_time_secs``): a backend compile at
#: least this long is counted among ``slow_compiles``.
WORTH_CACHING_S = 1.0
LONGEST = 5

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_SECONDS = {"trace": "trace_lower_s", "lower": "trace_lower_s", "hit": "cache_load_s",
            "miss": "backend_compile_s", "off": "backend_compile_s"}
_COUNTS = {"hit": "cache_hits", "miss": "cache_misses", "off": "cache_off"}


def _empty() -> dict:
    return {"trace_lower_s": 0.0, "backend_compile_s": 0.0, "cache_load_s": 0.0,
            "cache_hits": 0, "cache_misses": 0, "cache_off": 0}


class CompileLog:
    """The store behind the module's functions; the three ``on_*`` methods
    are the listeners jax calls, on whichever thread compiles."""

    def __init__(self, maxlen: int = LOG_MAX):
        self._records: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        # Per thread: the cache's events since its last backend record, and
        # its open events as [kind, nested traces so far].
        self._thread = threading.local()
        self.dropped = 0

    def on_start(self, event: str, value: float, **_) -> None:
        kind = _KINDS.get(event)
        if kind is not None:
            try:
                self._thread.open.append([kind, 0])
            except AttributeError:
                self._thread.open = [[kind, 0]]

    def on_event(self, event: str, **_) -> None:
        if event == _CACHE_ASKED:
            # jax asks whenever caching is enabled, a directory or none.
            import jax

            self._thread.cache = "miss" if jax.config.jax_compilation_cache_dir else "off"
        elif event == _CACHE_HIT:
            self._thread.cache = "hit"

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        end = time.perf_counter()
        thread = self._thread
        if event == _CACHE_RETRIEVAL:
            thread.retrieval_s = duration
            return
        kind = _KINDS.get(event)
        if kind is None:
            return
        still_open = getattr(thread, "open", [])
        # An event whose start the log did not see (it began listening
        # inside it) closes with nothing of its own on the stack.
        nested = still_open.pop()[1] if still_open and still_open[-1][0] == kind else 0
        if kind == "trace" and still_open:
            still_open[-1][1] += 1 + nested
            return
        record = {"kind": kind, "fun_name": kwargs.get("fun_name"), "start": end - duration, "end": end,
                  "thread": threading.get_ident(), "cause": spans.open_phase()}
        if kind == "backend":
            record["cache"] = getattr(thread, "cache", "off")
            record["retrieval_s"] = getattr(thread, "retrieval_s", 0.0)
            thread.cache, thread.retrieval_s = "off", 0.0
        else:
            record["nested"] = nested
        self.add(record)

    def add(self, record: dict) -> None:
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self.dropped += 1
            self._records.append(record)

    def log(self, since: Optional[float] = None, until: Optional[float] = None) -> list[dict]:
        """The records that ended in ``[since, until]``, in the order they ended."""
        with self._lock:
            records = list(self._records)
        return [r for r in records
                if (since is None or r["end"] >= since) and (until is None or r["end"] <= until)]

    def summary(self, since: Optional[float] = None, until: Optional[float] = None) -> dict:
        """Seconds and counts of the records that ended in ``[since,
        until]``, in all and by ``cause`` (``"none"`` for ``None``):
        ``trace_lower_s``; ``backend_compile_s`` (``cache`` ``miss`` or
        ``off``: what the backend compiled); ``cache_load_s`` (``hit``: read,
        decompress, deserialise, load); ``cache_hits``, ``cache_misses``,
        ``cache_off``; ``slow_compiles``, the compiled ones of
        ``WORTH_CACHING_S`` or more; ``longest_backend``, the ``LONGEST``
        longest backend records. A record's seconds are its self time, its
        length less the records inside it on its thread, so no instant of a
        thread is counted twice and the sums add up beside each other."""
        with self._lock:
            records, dropped = list(self._records), self.dropped
        total, by_cause, backend, slow, kept = _empty(), {}, [], 0, 0
        outermost: dict = {}  # thread -> its records not inside a later one, as (start, end)
        for r in records:
            stack, inside = outermost.setdefault(r["thread"], []), 0.0
            while stack and stack[-1][0] >= r["start"]:
                start, end = stack.pop()
                inside += end - start
            stack.append((r["start"], r["end"]))
            if (since is not None and r["end"] < since) or (until is not None and r["end"] > until):
                continue
            kept += 1
            seconds = max(r["end"] - r["start"] - inside, 0.0)
            cause = by_cause.setdefault(r["cause"] or "none", _empty())
            state = r.get("cache", r["kind"])
            for sums in (total, cause):
                sums[_SECONDS[state]] += seconds
                if r["kind"] == "backend":
                    sums[_COUNTS[state]] += 1
            if r["kind"] == "backend":
                backend.append(r)
                slow += state != "hit" and r["end"] - r["start"] >= WORTH_CACHING_S
        backend.sort(key=lambda r: r["start"] - r["end"])
        return {
            **total, "slow_compiles": slow, "by_cause": by_cause,
            "longest_backend": [
                {"fun_name": r["fun_name"], "seconds": r["end"] - r["start"], "cache": r["cache"], "cause": r["cause"]}
                for r in backend[:LONGEST]
            ],
            "records": kept, "dropped": dropped,
        }


_LOG = CompileLog()
_listening = False
_listen_lock = threading.Lock()


def listen() -> None:
    """Start the log: register its listeners with ``jax.monitoring`` (one
    for the cache's events, one for an event's start and one for its end),
    once a process however often it is called."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_listener(_LOG.on_event)
        jax.monitoring.register_scalar_listener(_LOG.on_start)
        jax.monitoring.register_event_duration_secs_listener(_LOG.on_duration)
        _listening = True


def log(since: Optional[float] = None, until: Optional[float] = None) -> list[dict]:
    """The process's records that ended in ``[since, until]``: :meth:`CompileLog.log`."""
    return _LOG.log(since, until)


def summary(since: Optional[float] = None, until: Optional[float] = None) -> dict:
    """Their seconds and counts, in all and by cause: :meth:`CompileLog.summary`."""
    return _LOG.summary(since, until)
