"""The train driver end to end at a toy size on the CPU: the result line,
the control (the program's int8 path) coming out not correct, a broken
timed path coming out not correct, the per-layer readers, and the entry
point's refusal to run without a chip."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import run as harness, tracered

from conftest import FIXTURES, ROOT, read_json

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def drive(bench, cell, *, trace=False, overrides=None, seed=2**31 + 9):
    return harness.run_cell(bench, cell, seed, 0.3, trace,
                            process_t0=time.perf_counter(), overrides=overrides)


def test_sound_run_prints_the_contracts_line(toy_bench, toy_cell):
    line = drive(toy_bench, toy_cell)
    assert LINE_KEYS <= set(json.loads(json.dumps(line)))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
    assert line["metrics"]["train_img_s_chip"]["unit"] == "img/s/chip"
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes", "memory_peak_origin",
            "memory_allocator_peak_bytes", "memory_program_bytes"} <= set(line["device"])
    assert {r["check"] for r in line["checks"]} == set(toy_cell["limits"])
    assert all(r["ok"] and "limit" in r and "value" in r for r in line["checks"])


def test_control_in_int8_comes_out_not_correct(toy_bench, toy_cell):
    control = read_json(FIXTURES, "toy", "limits.json")["control"]["train_config"]
    line = drive(toy_bench, toy_cell, overrides=control)
    assert line["correct"] is False
    assert "first_grad_rel_diff" in [r["check"] for r in line["checks"] if not r["ok"]]


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(
        toy_bench, toy_cell, monkeypatch):
    import sav_tpu.train.trainer as trainer_module

    monkeypatch.setattr(trainer_module.optax, "apply_updates", lambda params, updates: params)
    line = drive(toy_bench, toy_cell)
    assert line["correct"] is False
    rows = {r["check"]: r for r in line["checks"]}
    assert rows["update_norm_gap"]["value"] == pytest.approx(1.0)
    assert not rows["update_norm_gap"]["ok"]


def test_traced_run_reads_the_per_layer_metrics(toy_bench, toy_cell, monkeypatch):
    # The CPU's trace has no device plane; the reduction of the recorded
    # chip trace stands in for it, so that the readers have something to read.
    recorded = tracered.reduce(os.path.join(FIXTURES, "tiny_tpu.xplane.pb"))
    monkeypatch.setattr(tracered, "reduce", lambda path: recorded)
    line = drive(toy_bench, toy_cell, trace=True)
    assert line["device"]["busy_s"] == recorded["busy_s"] and line["device"]["window_s"] > 0
    assert {"trainer.slowest_window_ms", "device.idle_share.train",
            "model.attention_share", "model.matmul_share"} <= set(line["metrics"])
    # No CPU number under a device metric's name; no all-reduce on one chip.
    assert "device.mfu" not in line["metrics"] and "parallel.allreduce_ms" not in line["metrics"]
    assert len(line["breakdown"]["device_ops"]) <= 10 and line["breakdown"]["idle_gaps"]


def _python(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def test_without_a_chip_the_entry_point_exits_nonzero_and_prints_no_result(bench):
    cell = bench["workloads"][0]["name"]
    done = _python(["benchmark/run.py", "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert done.returncode == harness.EXIT_NO_CHIP
    assert done.stdout.strip() == ""


def test_memory_keys_name_where_each_number_comes_from(monkeypatch):
    from benchmark import device

    monkeypatch.setattr(device, "allocator_peak_bytes", lambda: 2_000)
    compiled_larger = device.memory_report({"resident_bytes": 1_000, "step_temp_bytes": 7_000})
    assert compiled_larger["memory_peak_bytes"] == compiled_larger["memory_program_bytes"] == 8_000
    assert compiled_larger["memory_allocator_peak_bytes"] == 2_000
    assert "temp_size_in_bytes" in compiled_larger["memory_peak_origin"]
    measured_larger = device.memory_report({"resident_bytes": 1_000, "step_temp_bytes": 500})
    assert measured_larger["memory_peak_bytes"] == 2_000
    assert measured_larger["memory_peak_origin"] == "allocator peak_bytes_in_use"


def test_mfu_is_read_from_the_traces_busy_time_not_from_the_rate():
    read = harness.load_reader("device.mfu")
    record = {
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "spans": {"traced_steps": 20},
        "counters": {"train_flops_per_image": 27.6e9, "images_per_step_per_chip": 256},
        # A rate that would give 200%: the reader must not look at it.
        "end_to_end": {"train_img_s_chip": 2 * 197e12 / 27.6e9},
    }
    busy_per_step = 0.105
    value = read(record, {"busy_s": 20 * busy_per_step})
    assert value == pytest.approx(100 * 27.6e9 * 256 / busy_per_step / 197e12)
    assert read(record, None) is None
    assert read(dict(record, spans={"traced_steps": None}), {"busy_s": 1.0}) is None
    assert read(dict(record, device={"platform": "cpu", "kind": "cpu"}), {"busy_s": 1.0}) is None


DP4 = """
import json, sys, time
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
from benchmark import run as harness, schema
from conftest import FIXTURES, read_json
import os
toy = os.path.join(FIXTURES, "toy")
mix = read_json(toy, "mix.json"); mix["train_config"]["layout_preset"] = "dp"
cell = {{"name": "toy.dp4", "chips": 4, "config": read_json(toy, "config.json"), "mix": mix,
        "limits": read_json(toy, "limits.json")["limits"]}}
bench = schema.load({root!r})
line = harness.run_cell(bench, cell, 5, 0.3, False, process_t0=time.perf_counter())
print(json.dumps(line))
"""


def test_data_parallel_cell_on_four_virtual_devices():
    done = _python(["-c", DP4.format(root=ROOT, tests=os.path.dirname(__file__))],
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
