#!/usr/bin/env python3
"""Read, on the chip, the numbers a cell's limits are set from.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 12 --control-seeds 3

One process: the program's sound runs over ``--seeds`` seeds, then the
control over ``--control-seeds`` of them, at the cell's own size with a
short window. The control is the program with the lower-precision path the
cell's limits file names switched on (``control.train_config``). Prints one
line per run with every number compared, then for each number the largest
the sound runs gave and the smallest the control gave. The benchmark's own
runs never run this; limits are set from its output by hand and written,
with the readings, into ``benchmark/limits/<cell>.json`` and PERF.md.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(HERE, "tools")]
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2_200_000_001)
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args(argv)

    from benchmark import device, run as harness, schema

    bench = schema.load(ROOT)
    cell = harness.load_cell(bench, args.workload)
    device.require_chips(cell["chips"])
    harness.place_compile_cache()
    with open(os.path.join(HERE, "limits", args.workload + ".json")) as f:
        control = json.load(f)["control"]

    def numbers(seed: int, overrides: dict) -> dict:
        with contextlib.redirect_stdout(sys.stderr):
            line = harness.run_cell(bench, cell, seed, args.seconds, False,
                                    process_t0=time.perf_counter(), overrides=overrides)
        return {row["check"]: row["value"] for row in line["checks"]}

    readings = {"program": [], "control": []}
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    for arm, arm_seeds, overrides in (
        ("program", seeds, {}),
        ("control", seeds[: args.control_seeds], control["train_config"]),
    ):
        for seed in arm_seeds:
            got = numbers(seed, overrides)
            readings[arm].append(got)
            print(json.dumps({"arm": arm, "seed": seed, "numbers": got}), flush=True)
    for name in readings["program"][0]:
        largest = max(r[name] for r in readings["program"])
        smallest = min((r[name] for r in readings["control"]), default=None)
        print(json.dumps({
            "number": name, "program_largest": largest, "control_smallest": smallest,
            "ratio": None if not smallest or not largest else smallest / largest,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
