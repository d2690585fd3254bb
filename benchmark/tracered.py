"""Reduction of a ``jax.profiler`` trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. A device plane
is named ``/device:TPU:<n>`` and its line ``XLA Ops`` holds one event per
executed HLO operation, with a start and a duration in nanoseconds; host
threads are lines of the plane ``/host:CPU``, where the harness's own
``jax.profiler.TraceAnnotation`` spans (all named ``bench:...``) appear.

``reduce`` returns, per chip and averaged over the chips used:

- ``busy_s``: the union of the intervals in which an operation ran;
- ``op_seconds``: device seconds by operation name. The trace prints an
  event's whole HLO instruction; the name is what stands before `` = ``,
  without the ``%`` (``fusion.1478``), which is the name the compiled
  module's text gives the instruction too;
- ``idle_gaps``: the gaps between operations on the first chip, summed by
  the harness span that overlaps the gap most (``unannotated`` where none
  does).

The events carry no scope on this stack (their stats are device offsets
only), so a metric that splits time by scope joins ``op_seconds`` with the
``op_name`` metadata of the compiled module's text: :func:`scopes_of_hlo`.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"


class TraceError(RuntimeError):
    pass


def find_xplane(profile_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def _union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for start, stop in sorted(intervals):
        if start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total * 1e-9


def _gaps(intervals):
    """``(start, stop)`` of every gap between merged intervals."""
    out, end = [], None
    for start, stop in sorted(intervals):
        if end is not None and start > end:
            out.append((end, start))
        end = stop if end is None else max(end, stop)
    return out


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scopes_of_hlo(hlo_text: str) -> dict:
    """Instruction name -> the ``op_name`` scope path of its metadata (""
    where it has none), for every instruction of a compiled module's text.
    A fusion carries the scope of its root."""
    scopes = {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found:
            scope = _OP_NAME.search(line)
            scopes.setdefault(found.group(1), scope.group(1) if scope else "")
    return scopes


def share_by_scope(trace: dict, scopes: dict, wanted) -> float:
    """Percent of the device's operation time in operations whose scope
    ``wanted(scope)`` accepts; an operation with no scope counts for none."""
    total = sum(trace["op_seconds"].values())
    hit = sum(s for name, s in trace["op_seconds"].items() if wanted(scopes.get(name, "")))
    return 100.0 * hit / total


def reduce(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    chips = []
    spans = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                    for ev in line.events
                ]
                chips.append(events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    chips = [c for c in chips if c]
    if not chips:
        raise TraceError(
            f"no operation ran on a device in {xplane_path}: planes "
            f"{[p.name for p in data.planes]}"
        )
    op_seconds = defaultdict(float)
    busy = 0.0
    for events in chips:
        busy += _union_seconds((s, e) for s, e, _ in events)
        for start, stop, name in events:
            op_seconds[name] += (stop - start) * 1e-9
    n = len(chips)
    gap_seconds = defaultdict(float)
    for start, stop in _gaps((s, e) for s, e, _ in chips[0]):
        overlaps = [(min(e, stop) - max(s, start), name) for s, e, name in spans if s < stop and e > start]
        gap_seconds[max(overlaps)[1] if overlaps else "unannotated"] += (stop - start) * 1e-9
    return {
        "chips": n,
        "busy_s": busy / n,
        "op_seconds": {k: v / n for k, v in op_seconds.items()},
        "idle_gaps": dict(gap_seconds),
    }


_LAYER_INDEX = re.compile(r"(block|layer)_\d+")


def by_scope(op_seconds: dict, scopes: dict) -> dict:
    """``op_seconds`` summed by scope, the layers of a stack folded into one
    (``block_3`` -> ``block_*``) and the jit wrapper's prefix dropped; an
    operation with no scope keeps its own name."""
    out = defaultdict(float)
    for name, seconds in op_seconds.items():
        scope = scopes.get(name, "")
        label = _LAYER_INDEX.sub(r"\1_*", scope.split("/", 1)[-1]) if scope else name
        out[label] += seconds
    return dict(out)


def top(table: dict, k: int = 10) -> list:
    return [[name, seconds] for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])[:k]]


class Tracer:
    """Starts and stops the profiler on request; the driver decides when."""

    def __init__(self, profile_dir: str):
        self.profile_dir = profile_dir

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        # The Python tracer records every function call of every thread:
        # the harness's spans are TraceMe events and do not need it.
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.profile_dir, profiler_options=options)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
