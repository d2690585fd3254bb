"""BENCHMARK.json keeps to the contract's limits, and every name in it
finds its files."""

import copy
import os

import pytest

from benchmark import schema

from conftest import ROOT


def test_benchmark_json_passes(bench):
    schema.check(bench)


def test_every_name_finds_its_files(bench):
    here = os.path.join(ROOT, "benchmark")
    for config in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, config["file"]))
    for cell in bench["workloads"]:
        assert os.path.isfile(os.path.join(here, "traffic", cell["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(here, "limits", cell["name"] + ".json"))
    for metric in bench["per_layer"]:
        assert os.path.isfile(os.path.join(here, "layer_metrics", metric["name"] + ".py"))


def _breach(bench, edit):
    broken = copy.deepcopy(bench)
    edit(broken)
    with pytest.raises(schema.SchemaError):
        schema.check(broken)


@pytest.mark.parametrize("edit", [
    lambda b: b["workloads"][0].update(name="has space"),
    lambda b: b["workloads"][0].update(name="x" * 65),
    lambda b: b["end_to_end"][0].update(unit="img per s"),
    lambda b: b["end_to_end"][0].update(unit="µs"),
    lambda b: b["configs"][0].update(source="s" * 201),
    lambda b: b["workloads"][0].update(why="two\nlines"),
    lambda b: b["end_to_end"][0].update(bound=0.2),
    lambda b: b["end_to_end"][0].update(source="program_counter"),
    lambda b: b["end_to_end"][0].update(why="no such key"),
    lambda b: b["per_layer"][0].update(moves="no_such_metric"),
    lambda b: b["per_layer"][0].update(workloads=["no_such_cell"]),
    lambda b: b["end_to_end"][0].update(workloads=[b["workloads"][0]["name"]]),
    lambda b: b["workloads"][0].update(chips=4),
    lambda b: b["workloads"][0].update(chips=2),
    lambda b: b["configs"][0]["reduced"].append("hidden_size"),
    lambda b: b["configs"][0].update(file="sav_tpu/models/vit.py"),
    lambda b: b.update(run_seconds=52),
    lambda b: b.update(command=["python3", "bench.py/../x"]),
    lambda b: b["end_to_end"].pop(1),
    lambda b: b["workloads"].append(dict(b["workloads"][0], name="twin")),
    lambda b: b.update(extra=1),
], ids=lambda f: "")
def test_breaches_are_refused(bench, edit):
    _breach(bench, edit)
