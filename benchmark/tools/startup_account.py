#!/usr/bin/env python3
"""Run a cell once, untraced, and add its ``setup_s`` up from inside.

    python3 benchmark/tools/startup_account.py --workload <cell> --seed <n> [--seconds 10]

``run.py``'s own ``main`` runs in this process; after it the tool reads what
the program kept of its start-up (the process timeline of
``sav_tpu/obs/spans.py`` and the compile log of
``sav_tpu/obs/compile_log.py``) and the harness's own marks (``phases_s``, the
line before the result line), and gives every moment of fit's thread between
the process's start and the window's opening to the innermost thing that
covers it: a trace, lowering, compile or cache load by the phase span that
caused it; else the phase span itself (its self time); else the harness's
phase between two marks (its own host work: draws, copies, the followed
steps' run, warm-up). The last line is that account as JSON, ``rows`` in
seconds, largest first. For PERF.md's table of where start-up goes. Not a
benchmark: the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import threading
from collections import defaultdict

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(HERE, "tools")]
sys.path.insert(0, ROOT)

KINDS = {"trace": "trace_lower", "lower": "trace_lower", "hit": "cache_load", "miss": "compile", "off": "compile"}
IMPORT = "sav:startup/import:"


def self_seconds(intervals: list) -> dict:
    """Seconds of each label of ``(label, start, end)`` intervals, every
    moment given to the innermost interval that covers it (the one that
    started last; an interval that outlasts the one it started in is cut to
    it)."""
    out, stack, cursor = defaultdict(float), [], 0.0
    for label, start, end in sorted(intervals, key=lambda i: (i[1], -i[2])):
        while stack and stack[-1][2] <= start:
            done = stack.pop()
            out[done[0]] += done[2] - cursor
            cursor = done[2]
        if stack:
            out[stack[-1][0]] += start - cursor
            end = min(end, stack[-1][2])
        stack.append((label, start, end))
        cursor = start
    while stack:
        done = stack.pop()
        out[done[0]] += done[2] - cursor
        cursor = done[2]
    return dict(out)


def account(process_t0: float, opened: float, phases_s: dict, timeline: list, records: list) -> dict:
    """``rows`` of ``self_seconds`` over the harness's phases (their marks
    counted back from the window's opening), the timeline's spans and the
    compile log's records, all cut at the window's opening."""
    names = list(phases_s)
    if "window_opened" not in names:
        raise ValueError(f"phases_s has no window_opened: {names}")
    names = names[: names.index("window_opened") + 1]
    intervals, end = [], opened
    for name in reversed(names):
        intervals.append(("harness:" + name, end - phases_s[name], end))
        end -= phases_s[name]
    intervals.append(("harness:before_first_mark", process_t0, end))
    intervals += [
        (IMPORT + "*" if name.startswith(IMPORT) else name, start, min(stop, opened))
        for name, start, stop in timeline if start < opened
    ]
    intervals += [
        (f"{KINDS[r.get('cache', r['kind'])]} caused by {r['cause']}", r["start"], r["end"])
        for r in records if r["end"] <= opened
    ]
    rows = self_seconds(intervals)
    setup_s = opened - process_t0
    return {
        "setup_s": setup_s,
        "rows": dict(sorted(rows.items(), key=lambda kv: -kv[1])),
        "remainder_s": setup_s - sum(rows.values()),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args(argv)

    from benchmark import run as harness

    printed = io.StringIO()
    try:
        with contextlib.redirect_stdout(printed):
            rc = harness.main([
                "--workload", args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds), "--trace", "0",
            ])
    finally:
        sys.stdout.write(printed.getvalue())
    if rc:
        return rc
    harness_line, result = (json.loads(line) for line in printed.getvalue().strip().splitlines()[-2:])

    from benchmark import hostspans, startuplog

    opened = harness.PROCESS_T0 + result["metrics"]["setup_s"]["value"]
    summary = startuplog.before_window({"window_opened_t": opened})
    records, at_exit = [], None
    if summary:
        from sav_tpu.obs import compile_log

        fit_thread = threading.get_ident()
        records = [r for r in compile_log.log(until=opened) if r["thread"] == fit_thread]
        # The whole process's, the reference's compiles after the window
        # too: how near the log's bound a run comes.
        at_exit = len(compile_log.log())
    found = account(harness.PROCESS_T0, opened, harness_line["phases_s"], hostspans.program_timeline(), records)
    found["compile_log"] = summary and {k: summary[k] for k in summary if k != "by_cause"}
    found["records_at_exit"] = at_exit
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
