#!/usr/bin/env python3
"""Run a training cell once and print, step by step, metrics of the program's
own log records through the followed steps, the warm-up and the window.

    python3 benchmark/tools/window_metrics.py --workload <cell> --seed <n> [--seconds 10]
        [--keys moe_rows_over_bound,moe_overflow_share]

The harness's window keeps a step's loss and nothing else; this tool hangs a
second listener on ``Trainer.fit``'s log records (``train_fit.Window``'s call)
and prints the chosen keys of every record beside the phase it fell in, then
the cell's result line. For questions the result line cannot answer: does the
traffic hold still through the window (the routed buffers' fill, the overflow
passes a step took)? Not a benchmark: the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
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
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--keys", default="moe_rows_over_bound,moe_overflow_share,moe_held_share")
    args = parser.parse_args(argv)
    keys = args.keys.split(",")

    from benchmark import device, run as harness, schema
    from benchmark.drivers import train_fit

    bench = schema.load(ROOT)
    cell = harness.load_cell(bench, args.workload)
    device.require_chips(cell["chips"])
    harness.place_compile_cache()

    rows = []
    window_call = train_fit.Window.__call__

    def listening(self, m: dict) -> None:
        if "loss" in m:
            rows.append({"step": int(m["step"]), "phase": self.phase, **{k: float(m[k]) for k in keys if k in m}})
        window_call(self, m)

    train_fit.Window.__call__ = listening
    try:
        line = harness.run_cell(bench, cell, args.seed, args.seconds, False, process_t0=time.perf_counter())
    finally:
        train_fit.Window.__call__ = window_call
    for row in rows:
        print(json.dumps(row), flush=True)
    print(json.dumps({"seed": args.seed, "correct": line["correct"], "metrics": line["metrics"],
                      "checks": {r["check"]: r["value"] for r in line["checks"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
