#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It checks ``BENCHMARK.json``, finds the cell's files by the
names there (configuration, traffic mix, limits, per-layer readers; this
file names none of them), refuses anything but the cell's number of TPU
chips, sets up, measures for ``--seconds`` and prints as the last line of
its standard output the JSON object the contract fixes, which also
carries each number compared beside its limit (``checks``). The line
before it is the harness's own: where set-up and the reference spent
their seconds.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 3


def _read_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(bench: dict, name: str) -> dict:
    """The cell's entry joined with its configuration, mix and limits files."""
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"no cell named {name!r} in BENCHMARK.json")
    cell = dict(entries[0])
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config"] = _read_json(config["file"])
    cell["mix"] = _read_json(HERE, "traffic", cell["traffic"] + ".json")
    cell["limits"] = _read_json(HERE, "limits", name + ".json")["limits"]
    return cell


def load_reader(metric_name: str):
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + metric_name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def place_compile_cache() -> None:
    """One fixed directory inside the checkout, unless the environment
    names one: the program's own rule, applied before the harness's first
    compile (the path is part of the cache's key)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    # The harness's own programs (seeded weights, the pool, the reference's
    # blocks) compile in under jax's floor of 1 s each and would otherwise be
    # compiled anew by every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             *, process_t0: float, overrides: dict | None = None) -> dict:
    """Drive the cell once and build the result line. Looks for no chip:
    ``main`` does that first."""
    from benchmark import compare, device, schema, tracered

    tracer = None
    if trace:
        profile_dir = os.path.join(HERE, "out", "profile", cell["name"])
        shutil.rmtree(profile_dir, ignore_errors=True)
        tracer = tracered.Tracer(profile_dir)
    driver = importlib.import_module("benchmark.drivers." + cell["mix"]["driver"])
    record = driver.run(cell, seed, seconds, tracer, overrides or {})

    correct, checks = compare.decide(record["numbers"], cell["limits"])
    print(json.dumps({"phases_s": record["phases_s"], "reference_s": record["reference_s"]}), flush=True)
    record["device"] = dict(device.describe(), **device.memory_report(record["memory"]))
    line = {
        "correct": correct,
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": {},
        "device": record["device"],
        # Beyond the contract's keys: each number compared beside its limit.
        "checks": checks,
        "reference_s": record["reference_s"],
    }

    def reports(metric: dict) -> bool:
        return cell["name"] in schema.cells_reporting(metric, bench)

    if not trace:
        values = dict(record["end_to_end"], setup_s=record["window_opened_t"] - process_t0)
        for metric in filter(reports, bench["end_to_end"]):
            line["metrics"][metric["name"]] = {"value": values[metric["name"]], "unit": metric["unit"]}
        return line

    reduced = tracered.reduce(tracered.find_xplane(tracer.profile_dir))
    line["device"]["busy_s"] = reduced["busy_s"]
    line["device"]["window_s"] = record["traced_window_s"]
    line["breakdown"] = {
        # By scope where the driver read the compiled program's, so that the
        # twelve layers' copies of one operation count as one line.
        "device_ops": tracered.top(tracered.by_scope(reduced["op_seconds"], record.get("hlo_scopes") or {})),
        "idle_gaps": tracered.top(reduced["idle_gaps"]),
    }
    for metric in filter(reports, bench["per_layer"]):
        value = load_reader(metric["name"])(record, reduced)
        if value is None:
            print(f"run.py: {metric['name']} found nothing to read in {cell['name']}", file=sys.stderr)
            continue
        line["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # ``python3 benchmark/run.py`` puts benchmark/ first on the path; the
    # harness and the program are both imported from the checkout's root.
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    sys.path.insert(0, ROOT)
    from benchmark import device, schema

    bench = schema.load(ROOT)
    cell = load_cell(bench, args.workload)
    try:
        device.require_chips(cell["chips"])
    except device.NoChipError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    place_compile_cache()
    line = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace), process_t0=PROCESS_T0)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
