"""``model.recompute_share``: on a hand-made table where the share is known,
on tables that rematerialise nothing (the image cells), and through
``run_cell`` on the toy token cell, whose stack and heads both recompute."""

import copy
import os
import time

import pytest

from benchmark import run as harness, tracered
from benchmark.drivers import train_tokens_fit

from conftest import FIXTURES, read_json

TOY = os.path.join(FIXTURES, "toy_ouro")
METRIC = "model.recompute_share"
STEP = "jit(_train_step_impl)/"
BACK = STEP + "transpose(jvp(OuroLM))/ut_loop/jvp(OuroLM)/ut_loop/checkpoint/"
SCOPES = {
    # instruction -> op_name, as tracered.scopes_of_hlo gives them
    "fusion.1": STEP + "jvp(OuroLM)/ut_loop/layer_1/GatedFFBlock_0/fc1/gate/dot_general",
    "fusion.2": BACK + "rematted_computation/layer_1/GatedFFBlock_0/fc1/gate/dot_general",
    "fusion.3": BACK + "layer_1/GatedFFBlock_0/fc1/gate/dot_general",
    "remat.4": BACK + "rematted_computation/layer_1/SelfAttentionBlock_0/SelfAttentionBlock_0/pallas_call",
    "fusion.5": STEP + "transpose(jvp(OuroLM))/lm_head/jvp(OuroLM)/lm_head/checkpoint/rematted_computation/exp",
    # the compiler joins fused instructions' names: one recomputed is enough
    "fusion.6": BACK + "layer_1/mlp_norm_in/mul;" + BACK + "rematted_computation/layer_1/mlp_norm_in/rsqrt",
    "fusion.7": STEP + "optimizer/mul",
    "copy.8": "",
    # a parameter called after the label is no recomputation
    "fusion.9": STEP + "jvp(OuroLM)/ut_loop/layer_1/fc2/rematted_computation",
}
OP_SECONDS = {"fusion.1": 4.0, "fusion.2": 4.0, "fusion.3": 8.0, "remat.4": 2.0, "fusion.5": 3.0,
              "fusion.6": 1.0, "fusion.7": 2.0, "copy.8": 1.0, "fusion.9": 1.0, "not-in-the-text.1": 4.0}


@pytest.fixture(scope="module")
def read():
    return harness.load_reader(METRIC)


def test_recompute_share_is_the_rematted_operations_part_of_the_step(read):
    # Recomputed: the stack's matmul 4, its kernel call 2, the head's 3, the fused norm 1.
    assert read({"hlo_scopes": SCOPES}, {"op_seconds": OP_SECONDS}) == pytest.approx(100 * 10.0 / 30.0)


def test_nothing_to_read_where_nothing_is_rematerialised(read):
    kept = {k: v.replace("rematted_computation/", "") for k, v in SCOPES.items()}
    assert read({"hlo_scopes": kept}, {"op_seconds": OP_SECONDS}) is None
    image = {"fusion.1": STEP + "jit(main)/jvp(ViT)/Encoder_0/block_3/FFBlock_0/fc1/dot_general", "copy.1": ""}
    assert read({"hlo_scopes": image}, {"op_seconds": {"fusion.1": 1.0, "copy.1": 1.0}}) is None
    assert read({"hlo_scopes": SCOPES}, None) is None
    assert read({"hlo_scopes": None}, {"op_seconds": OP_SECONDS}) is None
    assert read({}, {"op_seconds": OP_SECONDS}) is None


def _entered(bench, name, config, config_file, traffic):
    extended = copy.deepcopy(bench)
    extended["configs"].append({"name": config, "source": "a toy", "file": config_file, "reduced": [], "why": "toy"})
    extended["workloads"].append({"name": name, "config": config, "traffic": traffic, "chips": 1, "why": "toy"})
    next(m for m in extended["per_layer"] if m["name"] == METRIC)["workloads"].append(name)
    return extended


def _one_second_an_instruction(monkeypatch, driver):
    """``run_cell`` with a device trace in which every instruction of the
    step the driver compiled took one second (the CPU's trace has no device
    plane; the recorded v5e trace gives the rest of the reduction's keys)."""
    recorded = tracered.reduce(os.path.join(FIXTURES, "fit_v5e.xplane.pb"))
    seen = {}
    drive = driver.run

    def run(*args):
        seen["record"] = drive(*args)
        return seen["record"]

    monkeypatch.setattr(driver, "run", run)
    monkeypatch.setattr(
        tracered, "reduce", lambda path: {**recorded, "op_seconds": dict.fromkeys(seen["record"]["hlo_scopes"], 1.0)}
    )
    return seen


def test_the_toy_token_cell_reports_its_recomputed_share(bench, monkeypatch):
    cell = {
        "name": "toy.ouro_train", "chips": 1, "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"), "limits": read_json(TOY, "limits.json")["limits"],
    }
    assert cell["config"]["train"]["remat"] is True
    extended = _entered(bench, cell["name"], "toy_ouro", "benchmark/tests/fixtures/toy_ouro/config.json", "toy_tokens")
    seen = _one_second_an_instruction(monkeypatch, train_tokens_fit)
    line = harness.run_cell(extended, cell, 2**31 + 9, 0.3, True, process_t0=time.perf_counter())
    scopes = seen["record"]["hlo_scopes"]
    labelled = [s for s in scopes.values() if "/rematted_computation/" in s]
    assert any("/ut_loop/" in s for s in labelled) and any("/lm_head/" in s for s in labelled)
    value = line["metrics"][METRIC]
    assert value["unit"] == "%" and value["value"] == pytest.approx(100 * len(labelled) / len(scopes))
    assert 0 < value["value"] < 60


def test_the_toy_image_cell_reports_none(bench, toy_cell, monkeypatch, capsys):
    from benchmark.drivers import train_fit

    extended = _entered(bench, toy_cell["name"], "toy_vit", "benchmark/tests/fixtures/toy/config.json", "toy_train")
    _one_second_an_instruction(monkeypatch, train_fit)
    line = harness.run_cell(extended, toy_cell, 3, 0.3, True, process_t0=time.perf_counter())
    assert METRIC not in line["metrics"] and "model.matmul_share" in line["metrics"]
    assert f"{METRIC} found nothing to read in toy.train" in capsys.readouterr().err


def test_benchmark_json_lists_the_metric_for_the_token_cell_only(bench):
    metric = bench["per_layer"][-1]
    assert metric == {
        "name": METRIC, "unit": "%", "better": "lower", "source": "device_trace", "layer": "models",
        "moves": "train_img_s_chip", "workloads": ["ouro.train_resident_4k"],
    }
