"""The benchmark's own tests: run with ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q`` from the repository's root. They are no part of
``tests/`` (the program's suite) and drive the harness at toy sizes."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def bench():
    from benchmark import schema

    return schema.load(ROOT)


@pytest.fixture()
def toy_cell():
    """A train cell at toy size, joined as ``run.load_cell`` joins one."""
    toy = os.path.join(FIXTURES, "toy")
    return {
        "name": "toy.train",
        "chips": 1,
        "config": read_json(toy, "config.json"),
        "mix": read_json(toy, "mix.json"),
        "limits": read_json(toy, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    """BENCHMARK.json with the toy cell entered, as a later PR would enter
    a cell: one more configuration, one more workload."""
    import copy

    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_vit", "source": toy_cell["config"]["source"],
        "file": "benchmark/tests/fixtures/toy/config.json", "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_vit", "traffic": "toy_train", "chips": 1, "why": "toy",
    })
    return extended
