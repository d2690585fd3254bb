"""The Qwen3-Next cell's files: the token driver end to end at a toy size on
the CPU with the hybrid delta-rule family (sound run correct, the int8 control
not), the cut's parameter count from shapes, the FLOP counts and the two
kernels' floors against a hand count, the three readers on a hand-made table,
the accepted readers on this family's record, and the catalog row's keys
against the configuration's file. Nothing here counts the benchmark's cells
or names the last entries of a list: the next cell changes those."""

import copy
import json
import math
import os
import time

import pytest

from benchmark import run as harness, schema
from benchmark.flops import qwen3_next as flops

from conftest import FIXTURES, ROOT, read_json

TOY = os.path.join(FIXTURES, "toy_qwen3_next")
CELL = "qwen3next.train_ep16_4k"
NEW_METRICS = {
    "model.gdn_share": ("lower", "models"),
    "kernel.gated_delta_roofline_share": ("higher", "kernels"),
    "kernel.gated_attention_roofline_share": ("higher", "kernels"),
}
REDUCED = ["num_layers", "num_experts", "vocab_size"]


def published():
    return read_json(ROOT, "benchmark", "configs", "qwen3_next_80b_a3b.json")


@pytest.fixture()
def toy_cell():
    return {
        "name": "toy.qwen3_next_train", "chips": 1,
        "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"),
        "limits": read_json(TOY, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_qwen3_next", "source": "toy", "file": "benchmark/tests/fixtures/toy_qwen3_next/config.json",
        "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_qwen3_next", "traffic": "toy_tokens", "chips": 1, "why": "toy",
    })
    for metric in extended["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(toy_cell["name"])
    return extended


@pytest.mark.parametrize("arm", ["sound", "control"])
def test_token_driver_carries_the_hybrid_family_and_decides_correct(toy_bench, toy_cell, arm):
    control = read_json(TOY, "limits.json")["control"]["train_config"]
    line = harness.run_cell(toy_bench, toy_cell, 2**31 + 9, 0.3, False, process_t0=time.perf_counter(),
                            overrides=control if arm == "control" else None)
    assert {r["check"] for r in line["checks"]} == set(toy_cell["limits"])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
    failed = [r["check"] for r in line["checks"] if not r["ok"]]
    if arm == "sound":
        assert line["correct"] is True, failed
    else:
        assert line["correct"] is False and "first_grad_rel_diff" in failed and "update_rel_diff" in failed


def test_the_mix_the_recipe_and_the_registry_agree():
    config, mix = published(), read_json(ROOT, "benchmark", "traffic", "train_ep16_hybrid_resident_4k.json")
    assert mix["driver"] == "train_tokens_fit" and (mix["pool_batches"], mix["followed_steps"]) == (4, 3)
    assert (mix["warmup_log_windows"], mix["trace_log_windows"]) == (2, 4)
    train = mix["train_config"]
    assert train["aux_loss_weight"] == config["recipe"]["balance_alpha"] == 1e-3
    assert (train["log_every_steps"], train["warmup_epochs"], train["num_epochs"]) == (1, 0, 1)
    assert (train["base_lr"], train["end_lr"], train["weight_decay"], train["clip_grad_norm"]) == (3e-4, 1e-6, 0.1, 1.0)
    # ISSUE 37's mix: four sequences a step, or its stated fallback of two with the batch's numbers halved.
    batch = config["train"]["per_chip_batch"]
    assert batch in (4, 2) and (train["num_train_images"], train["lr_scaling_divisor"]) == (3 * batch, batch)
    assert config["recipe"]["entropy_weight"] == 0.0  # read by the driver, not by this family
    assert config["model_overrides"] == {"experts_held": [config["expert_offset"], config["num_experts"]]}
    assert config["train"]["remat"] is True
    assert (config["vocab_size"], config["num_layers"], config["sequence_length"]) == (18992, 4, 4096)
    from sav_tpu.models.registry import _REGISTRY

    cls, registered = _REGISTRY["qwen3_next_80b_a3b"]
    assert registered["bias_update_rate"] == config["recipe"]["bias_update_rate"] == 0.0
    source = {"embed_dim": "hidden_size", "num_layers": "num_hidden_layers", "mlp_ch": "intermediate_size",
              "expert_ch": "moe_intermediate_size", "num_experts": "num_experts_published",
              "top_k": "num_experts_per_tok", "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
              "full_attention_interval": "full_attention_interval"}
    assert {k: registered[k] for k in source} == {k: config[v] for k, v in source.items()}
    assert config["shared_expert_intermediate_size"] == config["moe_intermediate_size"]  # one width for both
    assert registered["gated_attention"] == {
        "num_heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
        "head_ch": config["head_dim"], "rotary_ch": int(config["head_dim"] * config["partial_rotary_factor"]),
    }
    assert registered["gated_delta"] == {
        "key_heads": config["linear_num_key_heads"], "heads": config["linear_num_value_heads"],
        "key_ch": config["linear_key_head_dim"], "value_ch": config["linear_value_head_dim"],
        "conv_width": config["linear_conv_kernel_dim"],
    }
    assert (registered["first_dense"], registered["mtp_modules"]) == (0, 0) and not config["mlp_only_layers"]
    assert registered["scoring"] == "softmax" and registered["shared_gate"] and registered["norm_offset"]
    assert config["norm_topk_prob"] is True and registered["routed_scale"] == 1.0


def test_the_cuts_parameters_are_the_files_arithmetic():
    """The tree the cell trains, counted from shapes alone: 625,667,136
    parameters, 10.01 GB of state at 16 bytes each."""
    import jax
    import jax.numpy as jnp

    from sav_tpu.models import create_model

    config = published()
    model = create_model(config["model_name"], num_classes=config["vocab_size"], dtype=jnp.bfloat16,
                         num_layers=config["num_layers"], **config["model_overrides"])
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = jax.eval_shape(lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))["params"]
    count = {k: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(v)) for k, v in tree.items()}
    assert count["layer_0"] == count["layer_1"] == count["layer_2"] == 138_582_208
    assert count["layer_3"] == 132_127_232
    assert count["embed"] + count["lm_head"] + count["final_norm"] == 77_793_280
    assert sum(count.values()) == 625_667_136
    for number in ("625,667,136", "138,582,208", "132,127,232", "77,793,280", "10.01 GB"):
        assert number in config["cut"]["arithmetic"]
    block = lambda layer, name: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree[layer][name]))
    assert block("layer_0", "GatedDeltaNetBlock_0") == 33_718_464
    assert block("layer_3", "GatedSelfAttentionBlock_0") == 27_263_488
    assert block("layer_0", "moe") + 2 * 2048 - 32 * 3_145_728 == 4_200_448  # beside the routed experts, with the two norms
    assert 16 * 625_667_136 / 1e9 == pytest.approx(10.01, abs=0.005)


# ------------------------------------------------------------------- counts


def test_flops_against_a_hand_count():
    config = published()
    # A delta-rule block, multiply-adds a token: in_proj_qkvz 2048x12288, in_proj_ba 2048x64, out_proj 4096x2048.
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert gdn == 33_685_504  # the block's 33,718,464 parameters less conv, A_log, dt_bias and the gated norm
    conv = 8192 * 4
    rule = 32 * (2 * 64 * 128 + 2 * 64 * 256 + 2 * 64 * 128 + 2 * 64 * 128 + 6 * 128 * 128)  # FLOP, not multiply-adds
    assert rule == 32 * 180_224 and flops.gated_delta_rule_flops_per_token_and_head(config) == 180_224
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048  # q (query and gate), k, v, o
    assert attention == 27_262_976  # the block's 27,263,488 less the two norms' 512
    core = 16 * 2 * 256 * 4097 / 2  # multiply-adds a position: (S + 1) / 2 visible pairs, logits and weighted sum
    expert, router, head = 3 * 2048 * 512, 2048 * 512, 2048 * 18992
    routed = 0.625 * expert  # 10 a token x 32 of 512 held
    token = 3 * (2 * (gdn + conv) + rule) + 2 * attention + 2 * core + 4 * 2 * (router + expert + 2048 + routed) + 2 * head
    assert flops.forward_flops_per_image(config) == pytest.approx(4096 * token, rel=1e-12)
    assert flops.train_flops_per_image(config) * 4 == pytest.approx(21.37e12, rel=1e-3)  # a step of 4 sequences
    owners = flops.forward_flops_by_owner(config)
    share = {k: round(100 * v / sum(owners.values()), 1) for k, v in owners.items()}
    assert share == {"gdn_projections": 46.5, "gdn_conv": 0.0, "gdn_rule": 4.0, "attention_projections": 12.5,
                     "attention_core": 7.7, "router": 1.9, "shared_experts": 5.8, "routed_experts": 3.6, "head": 17.9}
    assert flops.held_routings_per_token(config) == 0.625
    assert flops.layer_kinds(config) == {"full": 1, "linear": 3}
    assert flops.layer_kinds({**config, "num_layers": 48}) == {"full": 12, "linear": 36}


def test_the_two_kernels_floors_against_a_hand_count():
    config = published()
    tokens = 4 * 4096
    # The rule: q, k at 16 x 128 and v, o at 32 x 128 in bfloat16, g and beta float32 a value head.
    assert flops.gated_delta_rule_bytes_per_token(config) == (2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4 == 24_832
    by_flops, by_bytes = tokens * 32 * 180_224 / 197e12, tokens * 24_832 / 819e9
    assert by_bytes > by_flops and by_bytes / by_flops < 1.1  # within 10% of each other
    once = flops.gated_delta_floor_seconds(config, tokens, False, 197e12, 819e9)
    assert once == pytest.approx(3 * 3 * by_bytes) and once == pytest.approx(4.47e-3, rel=1e-2)  # three layers, forward + 2
    assert flops.gated_delta_floor_seconds(config, tokens, True, 197e12, 819e9) == pytest.approx(3 * 4 * by_bytes)
    # The attention: 16 query heads, 4 D S (S + 1) / 2 each, forward; 2.5 times that backward.
    pairs = 4096 * 4097 / 2
    floor = flops.attention_floor_seconds(config, 4, 197e12, 819e9)
    assert floor["forward"] == pytest.approx(4 * 16 * 4 * 256 * pairs / 197e12) and floor["forward_bound"] == "flops"
    assert floor["backward"] == pytest.approx(2.5 * floor["forward"]) and floor["backward_bound"] == "flops"
    assert flops.attention_forward_bytes(config) == 4096 * 256 * (2 * 16 + 2 * 2) * 2 + 4 * 4096 * 16
    assert flops.attention_backward_bytes(config) == 4096 * 256 * (4 * 16 + 4 * 2) * 2 + 4 * 4096 * 16
    # The grouped matmuls, at this family's keys.
    one = 2 * 5120 * 2048 * 512
    assert flops.grouped_matmul_flops(config, 5120) == {"forward": 3 * one, "backward": 6 * one}
    kernels, rows = 3 * 32 * 2048 * 512 * 2, 5120 * (2 * (2048 + 512) + 512 + 2048) * 2
    assert flops.grouped_matmul_floor_seconds(config, 5120, 197e12, 819e9)["forward"] == pytest.approx(
        max(3 * one / 197e12, (kernels + rows) / 819e9))


# ------------------------------------------------------------------ readers

STEP = "jit(_train_step_impl)/"
FWD = STEP + "jvp(JoyAILM)/"
BWD = STEP + "transpose(jvp(JoyAILM))/jvp(JoyAILM)/checkpoint/"
GDN, ATTN = "layer_1/GatedDeltaNetBlock_0/", "layer_3/GatedSelfAttentionBlock_0/"
SCOPES = {
    "qkvz.1": FWD + GDN + "to_qkv/qkvz/dot_general",
    "conv.2": FWD + GDN + "gdn/conv/mul",
    "rule.3": FWD + GDN + "gdn/rule/while/body/dot_general",
    "rule.4": BWD + "rematted_computation/" + GDN + "gdn/rule/while/body/dot_general",
    "rule.5": BWD + GDN + "gdn/rule/transpose(while)/body/dot_general",
    "norm.6": FWD + GDN + "gdn/gate_norm/mul",
    "out.7": BWD + GDN + "to_out/dot_general",
    "attn.8": FWD + ATTN + "pallas_call",
    "attn.9": BWD + ATTN + "pallas_call",
    "attn.10": BWD + ATTN + "reduce_sum",  # the group's dk summed after the call: no Mosaic call
    "qkv.11": FWD + ATTN + "to_qkv/q/dot_general",
    "gmm.12": FWD + "layer_1/moe/experts/fc1/jit(gmm)/pallas_call",
    "head.13": FWD + "lm_head/checkpoint/dot_general",
    "fusion.14": STEP + "optimizer/add",
    "fused.15": FWD + GDN + "gdn/rule/mul;" + FWD + GDN + "gdn/conv/mul",
}
OP_SECONDS = {"qkvz.1": 6.0, "conv.2": 1.0, "rule.3": 3.0, "rule.4": 3.0, "rule.5": 6.0, "norm.6": 1.0, "out.7": 2.0,
              "attn.8": 2.0, "attn.9": 5.0, "attn.10": 1.0, "qkv.11": 3.0, "gmm.12": 2.0, "head.13": 4.0,
              "fusion.14": 1.0, "fused.15": 1.0}
TOTAL = sum(OP_SECONDS.values())
BESIDE_THE_PROJECTIONS, IN_RULE = 15.0, 13.0  # conv, rule x3, norm, fused | rule x3, fused


def record(**over):
    base = {
        "hlo_scopes": SCOPES,
        "kernel_calls": {k: v for k, v in SCOPES.items() if v.endswith("pallas_call")},
        "config": published(),
        "spans": {"traced_steps": 2},
        "counters": {"images_per_step_per_chip": 4},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
    }
    return {**base, **over}


def another_familys_scopes():
    """A program without the two gated blocks: the parent's, another family's."""
    return {k: v.replace("GatedSelfAttentionBlock", "LatentSelfAttentionBlock")
            for k, v in SCOPES.items() if "GatedDeltaNetBlock" not in v}


def test_gdn_share_is_the_block_without_its_two_projections():
    read = harness.load_reader("model.gdn_share")
    trace = {"op_seconds": OP_SECONDS}
    assert read(record(), trace) == pytest.approx(100 * BESIDE_THE_PROJECTIONS / TOTAL)
    other = another_familys_scopes()
    assert read(record(hlo_scopes=other), {"op_seconds": {k: v for k, v in OP_SECONDS.items() if k in other}}) is None
    assert read(record(), None) is None and read(record(hlo_scopes=None), trace) is None


def test_gated_delta_roofline_share_counts_the_chunked_algorithm_whatever_runs():
    read = harness.load_reader("kernel.gated_delta_roofline_share")
    config, trace = published(), {"op_seconds": OP_SECONDS}
    least = 2 * flops.gated_delta_floor_seconds(config, 4 * 4096, True, 197e12, 819e9)  # two traced steps, recomputed
    assert read(record(), trace) == pytest.approx(100 * least / IN_RULE)
    kept = {k: v.replace("rematted_computation/", "") for k, v in SCOPES.items()}
    once = 2 * flops.gated_delta_floor_seconds(config, 4 * 4096, False, 197e12, 819e9)
    assert read(record(hlo_scopes=kept), trace) == pytest.approx(100 * once / IN_RULE)
    # Nothing to read: no such scope, another family's record, no chip, no trace.
    other = another_familys_scopes()
    assert read(record(hlo_scopes=other), {"op_seconds": {k: v for k, v in OP_SECONDS.items() if k in other}}) is None
    xing = read_json(ROOT, "benchmark", "configs", "xing4_29b_a4b.json")
    assert read(record(config=xing), trace) is None  # a family whose file counts no such rule
    assert read(record(config={}), trace) is None and read(record(hlo_scopes=None), trace) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), trace) is None
    assert read(record(), None) is None
    assert read({"spans": {}, "device": {"platform": "tpu"}}, trace) is None


def test_gated_attention_roofline_share_counts_one_backward_an_application():
    read = harness.load_reader("kernel.gated_attention_roofline_share")
    config, trace = published(), {"op_seconds": OP_SECONDS}
    floor = flops.attention_floor_seconds(config, 4, 197e12, 819e9)
    # One forward call and one backward call, 2 + 5 s over two traced steps; the reduce after the call is not a call.
    assert read(record(), trace) == pytest.approx(100 * 2 * (floor["forward"] + floor["backward"]) / 7.0)
    # The forward recomputed under remat: two forward floors, still one application's backward.
    again = dict(SCOPES, **{"attn.16": BWD + "rematted_computation/" + ATTN + "pallas_call"})
    calls = {k: v for k, v in again.items() if v.endswith("pallas_call")}
    seconds = {"op_seconds": dict(OP_SECONDS, **{"attn.16": 2.0})}
    assert read(record(hlo_scopes=again, kernel_calls=calls), seconds) == pytest.approx(
        100 * 2 * (2 * floor["forward"] + floor["backward"]) / 9.0)
    # Two backward calls (the two-kernel form) are one application's backward between them.
    two = dict(SCOPES, **{"attn.17": BWD + ATTN + "pallas_call"})
    calls = {k: v for k, v in two.items() if v.endswith("pallas_call")}
    seconds = {"op_seconds": dict(OP_SECONDS, **{"attn.17": 3.0})}
    assert read(record(hlo_scopes=two, kernel_calls=calls), seconds) == pytest.approx(
        100 * 2 * (floor["forward"] + floor["backward"]) / 10.0)
    # Nothing to read: another family's calls, another family's file, no chip, no trace.
    other = another_familys_scopes()
    calls = {k: v for k, v in other.items() if v.endswith("pallas_call")}
    assert read(record(hlo_scopes=other, kernel_calls=calls), trace) is None
    xing = read_json(ROOT, "benchmark", "configs", "xing4_29b_a4b.json")
    assert read(record(config=xing), trace) is None  # its calls lie under another block, and its file has no head_dim
    assert read(record(config={}), trace) is None and read(record(kernel_calls=None), trace) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), trace) is None
    assert read(record(), None) is None


def test_the_accepted_readers_read_the_new_scopes():
    trace = {"op_seconds": OP_SECONDS}
    # The gated attention's core: the two calls and the group's sum; the delta-rule block is no attention block.
    assert harness.load_reader("model.attention_share")(record(), trace) == pytest.approx(100 * 8.0 / TOTAL)
    assert harness.load_reader("kernel.attention_engaged_share")(record(), trace) == pytest.approx(100 * 7.0 / 8.0)
    # to_qkv and to_out of both blocks and the grouped matmuls
    assert harness.load_reader("model.matmul_share")(record(), trace) == pytest.approx(100 * 13.0 / TOTAL)
    assert harness.load_reader("model.unowned_share")(record(), trace) == 0.0
    assert harness.load_reader("trainer.optimizer_share")(record(), trace) == pytest.approx(100 * 1.0 / TOTAL)
    assert harness.load_reader("model.recompute_share")(record(), trace) == pytest.approx(100 * 3.0 / TOTAL)
    assert harness.load_reader("model.mtp_share")(record(), trace) is None  # no module
    # The expert family's readers find this family's record as they find their own: its flops file answers
    # every function they look up, its configuration every key.
    assert harness.load_reader("model.moe_routed_share")(record(), trace) == pytest.approx(100 * 2.0 / TOTAL)
    assert harness.load_reader("model.moe_dispatch_share")(record(), trace) == 0.0
    assert harness.load_reader("kernel.grouped_matmul_roofline_share")(record(), trace) is not None
    assert harness.load_reader("kernel.mla_attention_roofline_share")(record(), trace) is None  # no latent block


# ------------------------------------------------------------------- schema


def test_benchmark_json_holds_the_cell_and_its_files(bench):
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "train_tokens_fit"
    assert (cell["config"]["name"], cell["traffic"]) == ("qwen3_next_80b_a3b", "train_ep16_hybrid_resident_4k")
    config = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3_next_80b_a3b")
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert len(entry["source"]) <= 200 and entry["file"] == "benchmark/configs/qwen3_next_80b_a3b.json"
    assert entry["source"].startswith("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert (config["num_layers"], config["num_experts"], config["vocab_size"]) == (4, 32, 18992)
    assert (config["num_layers_published"], config["num_experts_published"], config["vocab_size_published"]) == (
        48, 512, 151936)
    assert config["num_hidden_layers"] == 48 and config["expert_offset"] == 0
    for key in ("what", "deployment", "arithmetic", "floors", "what_it_skews"):
        assert config["cut"][key]
    for key in ("mtp", "balance_term", "adam_b1_b2_eps", "peak_learning_rate", "weight_decay", "clip_grad_norm",
                "sequence_length", "documents", "attention_bias", "precision", "initial_values"):
        assert config["assumed"][key]
    assert set(cell["limits"]) >= {"first_grad_rel_diff", "update_rel_diff", "compiles_in_window"}
    for name, (better, layer) in NEW_METRICS.items():
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in metric["workloads"] and metric["moves"] == "train_img_s_chip"
        assert metric["source"] == "device_trace" and metric["unit"] == "%"
        assert (metric["better"], metric["layer"]) == (better, layer)
        assert callable(harness.load_reader(name))
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    fours = sum(w["chips"] == 4 for w in bench["workloads"])
    assert fours <= max(1, len(bench["workloads"]) // 4)  # the quarter rule, whatever the next cell adds


def test_every_key_of_the_catalog_row_is_in_the_file_or_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    config = published()
    differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
    # num_layers is a key of its own beside num_hidden_layers, which stays the published 48
    assert differs == set(REDUCED) - {"num_layers"}
    assert not [k for k in config["reduced"] if any(w in k for w in schema.WIDTH_WORDS)]
    for width in ("hidden_size", "head_dim", "linear_key_head_dim", "linear_value_head_dim", "moe_intermediate_size",
                  "shared_expert_intermediate_size", "num_experts_per_tok", "linear_conv_kernel_dim"):
        assert config[width] == row["config"][width]
