"""Driven by data: a later PR adds a configuration, a traffic mix, a cell
and a per-layer metric by adding files and entries only, edits no file that
is there, and the new cell runs. Done here in a temporary copy of
``BENCHMARK.json`` and ``benchmark/``."""

import json
import os
import shutil
import subprocess
import sys

from conftest import FIXTURES, ROOT, read_json

NEW_READER = '''"""Images the rate window trained on (program_counter)."""


def read(record, trace):
    return float(record["counters"]["images"])
'''

DRIVE = """
import json, os, sys, time
sys.path[:0] = [{copy!r}, {program!r}]
from benchmark import run as harness, schema, tracered
assert harness.ROOT == {copy!r}
recorded = tracered.reduce({xplane!r})
tracered.reduce = lambda path: recorded
bench = schema.load(harness.ROOT)
cell = harness.load_cell(bench, "toy2.train")
for trace in (False, True):
    print(json.dumps(harness.run_cell(bench, cell, 3, 0.3, trace, process_t0=time.perf_counter())))
"""


def test_a_cell_is_added_by_files_and_entries_only(tmp_path):
    copy = str(tmp_path / "checkout")
    os.makedirs(copy)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {
        os.path.relpath(os.path.join(d, f), copy): open(os.path.join(d, f), "rb").read()
        for d, _, files in os.walk(os.path.join(copy, "benchmark")) for f in files
    }
    here = os.path.join(copy, "benchmark")
    toy = os.path.join(FIXTURES, "toy")
    shutil.copy(os.path.join(toy, "config.json"), os.path.join(here, "configs", "toy2_vit.json"))
    shutil.copy(os.path.join(toy, "mix.json"), os.path.join(here, "traffic", "toy2_train.json"))
    shutil.copy(os.path.join(toy, "limits.json"), os.path.join(here, "limits", "toy2.train.json"))
    with open(os.path.join(here, "layer_metrics", "toy2.images.py"), "w") as f:
        f.write(NEW_READER)
    bench = read_json(copy, "BENCHMARK.json")
    bench["configs"].append({"name": "toy2_vit", "source": "a toy", "file": "benchmark/configs/toy2_vit.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "toy2.train", "config": "toy2_vit", "traffic": "toy2_train",
                               "chips": 1, "why": "toy"})
    bench["per_layer"].append({"name": "toy2.images", "unit": "img", "better": "higher",
                               "source": "program_counter", "layer": "trainer",
                               "moves": "train_img_s_chip", "workloads": ["toy2.train"]})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    script = DRIVE.format(copy=copy, program=ROOT, xplane=os.path.join(FIXTURES, "tiny_tpu.xplane.pb"))
    done = subprocess.run([sys.executable, "-c", script], cwd=copy, capture_output=True, text=True,
                          timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    timed, traced = [json.loads(l) for l in done.stdout.splitlines() if l.startswith('{"correct"')]
    assert timed["correct"] is True and set(timed["metrics"]) == {"train_img_s_chip", "setup_s"}
    assert traced["metrics"]["toy2.images"]["value"] > 0
    assert "parallel.allreduce_ms" not in traced["metrics"]  # lists other cells
    after = {path: open(os.path.join(copy, path), "rb").read() for path in before}
    assert after == before, "a file that was there was edited"


def test_without_the_program_the_harness_fails(tmp_path):
    copy = str(tmp_path / "bare")
    os.makedirs(copy)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    script = (
        "import sys, time; sys.path.insert(0, %r)\n"
        "from benchmark import run as harness, schema\n"
        "bench = schema.load(harness.ROOT); cell = harness.load_cell(bench, bench['workloads'][0]['name'])\n"
        "harness.run_cell(bench, cell, 1, 0.1, False, process_t0=time.perf_counter())\n" % copy
    )
    done = subprocess.run([sys.executable, "-c", script], cwd=copy, capture_output=True, text=True,
                          timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert done.returncode != 0 and '"correct"' not in done.stdout
    assert "sav_tpu" in done.stderr
