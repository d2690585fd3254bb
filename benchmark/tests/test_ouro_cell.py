"""The Ouro cell's files: the token driver end to end at a toy size on the
CPU (sound run correct, the int8 control not), the FLOP and roofline counts
against a hand count, the three readers on hand-made tables, and the
schema's verdict on ``BENCHMARK.json`` as this cell leaves it."""

import copy
import os
import time

import pytest

from benchmark import run as harness, schema
from benchmark.flops import ouro as flops

from conftest import FIXTURES, ROOT, read_json

TOY = os.path.join(FIXTURES, "toy_ouro")
CELL = "ouro.train_resident_4k"


@pytest.fixture()
def toy_cell():
    return {
        "name": "toy.ouro_train", "chips": 1,
        "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"),
        "limits": read_json(TOY, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_ouro", "source": toy_cell["config"]["source"],
        "file": "benchmark/tests/fixtures/toy_ouro/config.json", "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_ouro", "traffic": "toy_tokens", "chips": 1, "why": "toy",
    })
    for metric in extended["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(toy_cell["name"])
    return extended


def drive(bench, cell, *, overrides=None, seed=2**31 + 9):
    return harness.run_cell(bench, cell, seed, 0.3, False,
                            process_t0=time.perf_counter(), overrides=overrides)


@pytest.mark.parametrize("arm", ["sound", "control"])
def test_token_driver_decides_correct(toy_bench, toy_cell, arm):
    control = read_json(TOY, "limits.json")["control"]["train_config"]
    line = drive(toy_bench, toy_cell, overrides=control if arm == "control" else None)
    assert {r["check"] for r in line["checks"]} == set(toy_cell["limits"])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
    if arm == "sound":
        assert line["correct"] is True, [r for r in line["checks"] if not r["ok"]]
    else:
        assert line["correct"] is False
        assert "first_grad_rel_diff" in [r["check"] for r in line["checks"] if not r["ok"]]


def test_the_record_counts_sequences_and_tokens(toy_cell):
    from benchmark.drivers import train_tokens_fit

    record = train_tokens_fit.run(toy_cell, 3, 0.2, None, {})
    counters = record["counters"]
    assert counters["images_per_step_per_chip"] == 2
    assert counters["images"] == 2 * record["attempted"]
    assert counters["tokens"] == counters["images"] * 32
    assert counters["train_flops_per_image"] == flops.train_flops_per_image(toy_cell["config"])
    assert record["kernel_calls"] is None and record["hlo_scopes"] is None  # no trace asked for
    assert record["numbers"]["compiles_in_window"] == 0


def test_kernel_calls_are_read_from_the_compiled_text():
    from benchmark.drivers.train_tokens_fit import kernel_calls

    text = (
        '  %a.1 = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp(M)/ut_loop/layer_0/SelfAttentionBlock_0/pallas_call" stack_frame_id=3}\n'
        '  %b.2 = bf16[8] custom-call(%x), custom_call_target="Sharding"\n'
        '  ROOT %c.3 = bf16[8] custom-call(%y), custom_call_target="tpu_custom_call"\n'
    )
    assert kernel_calls(text) == {
        "a.1": "jit(f)/jvp(M)/ut_loop/layer_0/SelfAttentionBlock_0/pallas_call", "c.3": "",
    }


# ------------------------------------------------------------------- counts


def test_flops_against_a_hand_count():
    config = read_json(ROOT, "benchmark", "configs", "ouro_2.6b.json")
    # A layer application, per token: Q, K, V, out 4 x 2048^2 and SwiGLU
    # 3 x 2048 x 5632 multiply-adds, twice that in FLOPs; causal attention
    # 4 x 128 x 16 heads x (4096 + 1) / 2.
    matmul = 2 * (4 * 2048**2 + 3 * 2048 * 5632)
    attention = 4 * 128 * 16 * 4097 / 2
    assert matmul == 102_760_448 and attention == 16_781_312
    layer = 4096 * (matmul + attention)
    assert flops.layer_application_flops_per_sequence(config) == pytest.approx(layer, rel=1e-12)
    head = 2 * 4096 * 2048 * 49152
    forward = 4 * (4 * layer + head)
    assert flops.forward_flops_per_image(config) == pytest.approx(forward, rel=1e-12)
    assert forward / 4096 == pytest.approx(2.717e9, rel=1e-3)  # ISSUE 26's 2.72 GFLOP a token
    assert flops.train_flops_per_image(config) * 2 == pytest.approx(66.8e12, rel=2e-3)  # a step of 2 sequences


def test_kernel_floor_against_a_hand_count():
    config = read_json(ROOT, "benchmark", "configs", "ouro_2.6b.json")
    fwd_flops = 4 * 128 * 4096 * 4097 / 2
    assert flops.causal_attention_forward_flops(4096, 128) == fwd_flops
    assert flops.causal_attention_backward_flops(4096, 128) == 2.5 * fwd_flops
    assert flops.causal_attention_forward_bytes(4096, 128) == 4 * 4096 * 128 * 2 + 4 * 4096
    assert flops.causal_attention_backward_bytes(4096, 128) == 8 * 4096 * 128 * 2 + 4 * 4096
    floor = flops.causal_attention_floor_seconds(config, 2, 197e12, 819e9)
    # 2 sequences x 16 heads: the FLOPs bound both directions on a v5e.
    assert floor["forward"] == pytest.approx(32 * fwd_flops / 197e12) and floor["forward_bound"] == "flops"
    assert floor["backward"] == pytest.approx(2.5 * floor["forward"]) and floor["backward_bound"] == "flops"
    assert floor["forward"] == pytest.approx(0.698e-3, rel=1e-2)
    slow_memory = flops.causal_attention_floor_seconds(config, 2, 197e12, 1e9)
    assert slow_memory["forward_bound"] == "bytes"


# ------------------------------------------------------------------ readers

STEP = "jit(_train_step_impl)/"
ATTN = "ut_loop/layer_1/SelfAttentionBlock_0/SelfAttentionBlock_0/pallas_call"
SCOPES = {
    "fwd.1": STEP + "jvp(OuroLM)/" + ATTN,
    "remat.2": STEP + "transpose(jvp(OuroLM))/ut_loop/jvp(OuroLM)/ut_loop/checkpoint/rematted_computation/" + ATTN[8:],
    "dq.3": STEP + "transpose(jvp(OuroLM))/ut_loop/jvp(OuroLM)/ut_loop/checkpoint/" + ATTN[8:],
    "dkv.4": STEP + "transpose(jvp(OuroLM))/ut_loop/jvp(OuroLM)/ut_loop/checkpoint/" + ATTN[8:],
    "fusion.5": STEP + "jvp(OuroLM)/ut_loop/layer_1/GatedFFBlock_0/fc1/gate/dot_general",
    "fusion.6": STEP + "jvp(OuroLM)/lm_head/while/body/closed_call/dot_general",
    "fusion.7": STEP + "transpose(jvp(OuroLM))/lm_head/while/body/closed_call/checkpoint/rematted_computation/exp",
    "fusion.8": STEP + "jvp(loss)/reduce_sum",
    "fusion.9": STEP + "optimizer/mul",
    "fusion.10": STEP + "jvp(OuroLM)/exit_gate/dot_general",
}
OP_SECONDS = {"fwd.1": 2.0, "remat.2": 2.0, "dq.3": 3.0, "dkv.4": 5.0, "fusion.5": 8.0,
              "fusion.6": 4.0, "fusion.7": 3.0, "fusion.8": 1.0, "fusion.9": 1.0, "fusion.10": 1.0}


def record(**over):
    config = read_json(ROOT, "benchmark", "configs", "ouro_2.6b.json")
    base = {
        "hlo_scopes": SCOPES,
        "kernel_calls": {k: v for k, v in SCOPES.items() if v.endswith("pallas_call")},
        "config": config,
        "spans": {"traced_steps": 2},
        "counters": {"images_per_step_per_chip": 2},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
    }
    return {**base, **over}


def test_lm_head_share_is_the_heads_and_the_loss():
    read = harness.load_reader("model.lm_head_share")
    assert read(record(), {"op_seconds": OP_SECONDS}) == pytest.approx(100 * 8.0 / 30.0)
    vision = {k: v.replace("lm_head", "head") for k, v in SCOPES.items()}
    assert read(record(hlo_scopes=vision), {"op_seconds": OP_SECONDS}) is None
    assert read(record(), None) is None and read(record(hlo_scopes=None), {"op_seconds": OP_SECONDS}) is None


def test_loop_pass_ms_is_the_stacks_time_a_step_and_pass():
    read = harness.load_reader("model.loop_pass_ms")
    # 20 s under ut_loop over 2 steps and 4 passes
    assert read(record(), {"op_seconds": OP_SECONDS}) == pytest.approx(1e3 * 20.0 / 2 / 4)
    assert read(record(config=None), {"op_seconds": OP_SECONDS}) is None
    no_loop = {k: v.replace("ut_loop", "Encoder_0") for k, v in SCOPES.items()}
    assert read(record(hlo_scopes=no_loop), {"op_seconds": OP_SECONDS}) is None
    assert read(record(spans={}), {"op_seconds": OP_SECONDS}) is None


def test_roofline_share_counts_forwards_and_backward_pairs():
    read = harness.load_reader("kernel.causal_attention_roofline_share")
    config = read_json(ROOT, "benchmark", "configs", "ouro_2.6b.json")
    floor = flops.causal_attention_floor_seconds(config, 2, 197e12, 819e9)
    # two forward calls (first and recomputed), one backward of two kernels, two steps
    least = 2 * (2 * floor["forward"] + floor["backward"])
    assert read(record(), {"op_seconds": OP_SECONDS}) == pytest.approx(100 * least / 12.0)
    assert read(record(kernel_calls={}), {"op_seconds": OP_SECONDS}) is None  # the dense path
    assert read(record(kernel_calls=None), {"op_seconds": OP_SECONDS}) is None  # another driver, the parent
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), {"op_seconds": OP_SECONDS}) is None
    assert read({"spans": {}, "device": {"platform": "tpu"}}, {"op_seconds": OP_SECONDS}) is None


# ------------------------------------------------------------------- schema


def test_benchmark_json_holds_the_cell_and_its_files(bench):
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "train_tokens_fit"
    config = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2.6b")
    assert entry["reduced"] == config["reduced"] == ["num_layers"]
    published = {"hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
                 "intermediate_size": 5632, "vocab_size": 49152, "total_ut_steps": 4, "rope_theta": 1000000,
                 "rms_norm_eps": 1e-06, "num_hidden_layers": 48, "max_position_embeddings": 65536}
    assert {k: config[k] for k in published} == published
    assert config["num_layers"] == 4 and config["num_layers_published"] == 48
    assert set(cell["limits"]) >= {"first_grad_rel_diff", "update_rel_diff", "compiles_in_window"}
    for name in ("model.lm_head_share", "model.loop_pass_ms", "kernel.causal_attention_roofline_share"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_img_s_chip"
        assert callable(harness.load_reader(name))
    assert bench["workloads"][-1]["name"] == CELL and len(bench["workloads"]) == 4


def test_schema_refuses_the_depth_under_its_published_key(bench):
    broken = copy.deepcopy(bench)
    broken["configs"][-1]["reduced"] = ["num_hidden_layers"]
    with pytest.raises(schema.SchemaError, match="names a width"):
        schema.check(broken)
