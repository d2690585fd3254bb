"""The program's own host spans, read from a run's profiler trace.

``sav_tpu/obs/spans.py`` emits every phase of ``Trainer.fit`` and of the
feeder as a ``jax.profiler.TraceAnnotation`` named ``sav:<layer>/<phase>``;
under a profiler session they are events of the ``/host:CPU`` plane, on
the clock of the device's ``XLA Ops``. :func:`read` returns those events of
every host thread and the first chip's gaps; the functions below it reduce
them to what the per-layer readers report. A program that emits no such
span (the parent of the PR that added them) gives empty lists, and the
readers then report nothing.

A reader gets ``(record, trace)`` and the record carries no path, so
:func:`of_this_run` takes the newest ``.xplane.pb`` under
``benchmark/out/profile/``: ``run_cell`` cleared the cell's directory and
the profiler wrote there seconds before, in this process.

As a command it prints one trace's account, for PERF.md's split of the log
boundary's gap:

    python3 benchmark/hostspans.py [<trace>.xplane.pb]
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import tracered  # noqa: E402

PROFILE_ROOT = os.path.join(HERE, "out", "profile")
PREFIX = "sav:"
BOUNDARY = "sav:fit/log_boundary"
SYNC = "sav:fit/log_sync"
# Where fit's thread waits for the device or the feeder; in every other
# moment of a step the host is doing work of its own.
WAITS = ("sav:fit/batch_wait", "sav:fit/run_ahead_wait", SYNC)
# How far apart one trace's host and device planes may be for a boundary's
# gap still to be found: under a fifth of the shortest cell's step.
SKEW_NS = 20e6


def newest_xplane():
    found = glob.glob(os.path.join(PROFILE_ROOT, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def read(xplane_path: str) -> dict:
    """``spans``: ``(start_ns, end_ns, name, thread)`` of every ``sav:``
    event, the thread being its line's index in the host plane; ``gaps``:
    ``(start_ns, end_ns)`` between the first chip's operations."""
    from jax.profiler import ProfileData

    spans, first_chip = [], None
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name == tracered.HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                spans += [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, thread)
                    for ev in line.events if ev.name.startswith(PREFIX)
                ]
        elif first_chip is None and tracered.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == tracered.OPS_LINE:
                    first_chip = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events] or None
    return {"spans": sorted(spans), "gaps": tracered._gaps(first_chip or [])}


def of_this_run():
    """The newest trace's spans and gaps; None where no trace was written
    or it holds no ``sav:`` span."""
    path = newest_xplane()
    if path is None:
        return None
    found = read(path)
    return found if found["spans"] else None


def program_timeline() -> list:
    """The program's process timeline, ``[(name, start, end)]`` on
    ``time.perf_counter`` (``sav_tpu.obs.spans.timeline``, read in the
    program's own process); empty where the program keeps none."""
    try:
        from sav_tpu.obs import spans
    except ImportError:
        return []
    return spans.timeline() if hasattr(spans, "timeline") else []


def _overlap(a, b) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def boundary_gaps(found: dict) -> list:
    """For each ``sav:fit/log_boundary`` span of the trace, ``(boundary,
    its gaps)``. The boundary's ``device_get`` (its ``log_sync`` child)
    returns when the device has run dry, so the boundary's gaps are those
    that overlap it from that return on: what the device waited while the
    host logged and came round to its next dispatch. Gaps between the steps
    that were still queued while ``log_sync`` waited are not the
    boundary's. A span is in the trace only if the session saw it open and
    close: the boundaries whose ``log_fn`` started or stopped the profiler
    are not.

    The device cannot be busy at that return: where no gap holds it, the
    trace's host and device planes are apart (seen: by milliseconds, in
    two traces of four), and the boundary's gap is then taken to be the
    longest one within ``SKEW_NS`` of where it should lie; the steps' own
    gaps are a hundredth of it."""
    out = []
    for boundary, drained in _boundaries(found):
        if _held(found, drained):
            gaps = [g for g in found["gaps"] if _overlap(g, drained) > 0]
        else:
            near = (drained[0] - SKEW_NS, drained[1] + SKEW_NS)
            gaps = sorted((g for g in found["gaps"] if _overlap(g, near) > 0), key=lambda g: g[0] - g[1])[:1]
        out.append((boundary, gaps))
    return out


def _boundaries(found: dict) -> list:
    """``(boundary, (its log_sync's end, its own end))`` for each whole
    boundary of the trace."""
    out = []
    for boundary in (s for s in found["spans"] if s[2] == BOUNDARY):
        syncs = [s for s in found["spans"]
                 if s[2] == SYNC and s[3] == boundary[3] and boundary[0] <= s[0] and s[1] <= boundary[1]]
        out.append((boundary, (syncs[0][1] if syncs else boundary[0], boundary[1])))
    return out


def _held(found: dict, drained) -> bool:
    return any(g[0] <= drained[0] <= g[1] for g in found["gaps"])


def aligned(found: dict) -> bool:
    """Whether a gap holds the end of every boundary's ``log_sync``, so
    that the gaps' split among the host's spans means what it says."""
    return all(_held(found, drained) for _, drained in _boundaries(found))


def gap_ms_per_boundary(found: dict):
    per_boundary = boundary_gaps(found)
    if not per_boundary:
        return None
    return sum(g[1] - g[0] for _, gaps in per_boundary for g in gaps) * 1e-6 / len(per_boundary)


def split_of_gaps(found: dict) -> dict:
    """Nanoseconds of the boundaries' gaps by what fit's thread was in:
    the innermost ``sav:`` span at each moment, ``unspanned`` where none."""
    out = defaultdict(float)
    for boundary, gaps in boundary_gaps(found):
        thread = [s for s in found["spans"] if s[3] == boundary[3]]
        for gap in gaps:
            inside = [s for s in thread if _overlap(s, gap) > 0]
            edges = sorted({gap[0], gap[1], *(t for s in inside for t in s[:2] if gap[0] < t < gap[1])})
            for piece in zip(edges, edges[1:]):
                holders = [s for s in inside if s[0] <= piece[0] and piece[1] <= s[1]]
                # The innermost of nested spans is the one that opened last.
                out[max(holders)[2] if holders else "unspanned"] += piece[1] - piece[0]
    return dict(out)


def wait_seconds(found: dict) -> float:
    return sum(s[1] - s[0] for s in found["spans"] if s[2] in WAITS) * 1e-9


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    path = args[0] if args else newest_xplane()
    if path is None:
        print("hostspans.py: no .xplane.pb under " + PROFILE_ROOT, file=sys.stderr)
        return 1
    found = read(path)
    origin = found["spans"][0][0] if found["spans"] else 0.0
    seconds = defaultdict(float)
    counts = defaultdict(int)
    for start, end, name, _ in found["spans"]:
        seconds[name] += (end - start) * 1e-9
        counts[name] += 1
    print(json.dumps({
        "trace": path,
        "trace_bytes": os.path.getsize(path),
        "spans": {name: {"count": counts[name], "seconds": seconds[name]} for name in sorted(seconds)},
        "boundaries": len(boundary_gaps(found)),
        "boundary_gap_ms": gap_ms_per_boundary(found),
        "planes_aligned": aligned(found),
        "gap_split_ms": {k: v * 1e-6 for k, v in sorted(split_of_gaps(found).items(), key=lambda kv: -kv[1])},
        "first_chip_gaps_ms": sum(g[1] - g[0] for g in found["gaps"]) * 1e-6,
        # Where the long gaps lie against the boundaries, on the trace's
        # clock from its first span on: a boundary whose gap is not under
        # it shows here.
        "largest_gaps_ms": [
            {"start": (g[0] - origin) * 1e-6, "length": (g[1] - g[0]) * 1e-6}
            for g in sorted(found["gaps"], key=lambda g: g[0] - g[1])[:5]
        ],
        "boundaries_ms": [
            {"start": (b[0] - origin) * 1e-6, "end": (b[1] - origin) * 1e-6}
            for b, _ in boundary_gaps(found)
        ],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
