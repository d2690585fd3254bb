#!/usr/bin/env python3
"""Record the small chip trace that ``benchmark/tests/test_hostspans.py``
reads: the tests' toy cell (``tests/fixtures/toy``) through ``run_cell``
with the profiler on, two traced log windows of two steps, so that one
whole log boundary of ``fit`` lies inside the trace, as in a real cell.

    chiprun -- python3 benchmark/tools/record_fit_trace.py

The ``.xplane.pb`` is copied to ``chiprun_out/fit_v5e.xplane.pb`` and its
account by ``hostspans.py`` and ``tracered.py`` is printed, to pin the tests'
numbers on. A toy's numbers are a fixture's, never a result. That file is
a megabyte, most of it the compiled programs' protos and per-operation
statistics that no reader here looks at; back in the sandbox,

    python3 benchmark/tools/record_fit_trace.py --shrink chiprun_out/fit_v5e.xplane.pb \
        benchmark/tests/fixtures/fit_v5e.xplane.pb

keeps what the reductions read and nothing else: the first chip's ``XLA
Ops`` line (every event's name, start and duration) and the host plane's
``sav:`` and ``bench:`` events. Times and names are untouched. (It needs
the ``xplane_pb2`` module that TensorFlow ships; nothing else here does.)
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(HERE, "tools")]
sys.path.insert(0, ROOT)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def shrink(src: str, dst: str) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark import tracered

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    device_kept = False
    for plane in space.planes:
        is_device = bool(tracered.DEVICE_PLANE.match(plane.name))
        if not (plane.name == tracered.HOST_PLANE or (is_device and not device_kept)):
            continue
        device_kept = device_kept or is_device
        kept = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if is_device and line.name != tracered.OPS_LINE:
                continue
            events = [
                ev for ev in line.events
                if is_device or plane.event_metadata[ev.metadata_id].name.startswith(("sav:", "bench:"))
            ]
            if not events:
                continue
            kept_line = kept.lines.add(id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            for ev in events:
                kept_line.events.add(metadata_id=ev.metadata_id, offset_ps=ev.offset_ps,
                                     duration_ps=ev.duration_ps)
                meta = plane.event_metadata[ev.metadata_id]
                kept.event_metadata[ev.metadata_id].id = meta.id
                kept.event_metadata[ev.metadata_id].name = meta.name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{src}: {os.path.getsize(src)} bytes -> {dst}: {os.path.getsize(dst)} bytes")
    return 0


def main() -> int:
    if sys.argv[1:2] == ["--shrink"]:
        return shrink(*sys.argv[2:4])
    from benchmark import hostspans, run as harness, schema, tracered

    toy = os.path.join(HERE, "tests", "fixtures", "toy")
    mix = read_json(toy, "mix.json")
    mix["trace_log_windows"] = 2
    cell = {"name": "toy.train", "chips": 1, "config": read_json(toy, "config.json"), "mix": mix,
            "limits": read_json(toy, "limits.json")["limits"]}
    bench = schema.load(ROOT)
    bench["workloads"].append({"name": "toy.train", "config": "toy_vit", "traffic": "toy_train",
                               "chips": 1, "why": "toy"})
    harness.place_compile_cache()
    try:
        line = harness.run_cell(bench, cell, 2**31 + 24, 0.3, True, process_t0=time.perf_counter())
        print(json.dumps(line))
    except tracered.TraceError as e:  # the CPU's trace has no device plane
        print(f"record_fit_trace.py: {e}", file=sys.stderr)
    path = hostspans.newest_xplane()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "fit_v5e.xplane.pb"))
    return hostspans.main([path])


if __name__ == "__main__":
    sys.exit(main())
