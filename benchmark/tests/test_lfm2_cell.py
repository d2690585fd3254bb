"""The LFM2 cell's files: the token driver end to end at a toy size on the CPU
with the convolution-attention hybrid family (sound run correct, the int8
control not), the cut's parameter count from shapes, the FLOP counts and the
two floors against a hand count, the three readers on a hand-made table, the
accepted readers on this family's record, and the catalog row's keys against
the configuration's file. Nothing here counts the benchmark's cells or names
the last entries of a list: the next cell changes those."""

import copy
import json
import math
import os
import time

import pytest

from benchmark import run as harness, schema
from benchmark.flops import lfm2 as flops

from conftest import FIXTURES, ROOT, read_json

TOY = os.path.join(FIXTURES, "toy_lfm2")
CELL = "lfm2.train_ep8_8k"
NEW_METRICS = {
    "model.short_conv_share": ("lower", "models"),
    "kernel.short_conv_roofline_share": ("higher", "kernels"),
    "kernel.gqa64_attention_roofline_share": ("higher", "kernels"),
}
REDUCED = ["num_layers", "num_dense_layers", "num_experts", "vocab_size"]
HELD_KINDS = ["conv", "full_attention", "conv", "conv", "conv"]


def published():
    return read_json(ROOT, "benchmark", "configs", "lfm2_24b_a2b.json")


@pytest.fixture()
def toy_cell():
    return {
        "name": "toy.lfm2_train", "chips": 1,
        "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"),
        "limits": read_json(TOY, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_lfm2", "source": "toy", "file": "benchmark/tests/fixtures/toy_lfm2/config.json",
        "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_lfm2", "traffic": "toy_tokens", "chips": 1, "why": "toy",
    })
    for metric in extended["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(toy_cell["name"])
    return extended


@pytest.mark.parametrize("arm", ["sound", "control"])
def test_token_driver_carries_the_conv_family_and_decides_correct(toy_bench, toy_cell, arm):
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
        assert line["correct"] is False and {"first_grad_norm_gap", "first_grad_rel_diff", "update_rel_diff"} <= set(failed)


def test_the_mix_the_recipe_and_the_registry_agree():
    config, mix = published(), read_json(ROOT, "benchmark", "traffic", "train_ep8_conv_resident_8k.json")
    assert mix["driver"] == "train_tokens_fit" and (mix["pool_batches"], mix["followed_steps"]) == (4, 3)
    assert (mix["warmup_log_windows"], mix["trace_log_windows"]) == (2, 4)
    train = mix["train_config"]
    assert train["aux_loss_weight"] == config["recipe"]["balance_alpha"] == 1e-4
    assert (train["log_every_steps"], train["warmup_epochs"], train["num_epochs"]) == (1, 0, 1)
    assert (train["base_lr"], train["end_lr"], train["weight_decay"], train["clip_grad_norm"]) == (3e-4, 1e-6, 0.1, 1.0)
    # ISSUE 39's mix: four sequences a step, or its stated fall-backs with the batch's numbers.
    batch = config["train"]["per_chip_batch"]
    assert batch in (4, 3, 2) and (train["num_train_images"], train["lr_scaling_divisor"]) == (3 * batch, batch)
    assert config["recipe"]["entropy_weight"] == 0.0  # read by the driver, not by this family
    assert config["model_overrides"] == {
        "experts_held": [config["expert_offset"], config["num_experts"]],
        "first_dense": config["num_dense_layers"], "mixers": config["layer_types_held"],
    }
    assert config["first_k_dense_replace"] == config["num_dense_layers"] == 1 and config["num_nextn_predict_layers"] == 0
    assert config["layer_types_held"] == config["layer_types"][1:6] == HELD_KINDS  # published layers 1-5
    assert config["train"]["remat"] is True
    assert (config["vocab_size"], config["num_layers"], config["sequence_length"]) == (8192, 5, 8192)
    from sav_tpu.models.registry import _REGISTRY

    cls, registered = _REGISTRY["lfm2_24b_a2b"]
    assert registered["bias_update_rate"] == config["recipe"]["bias_update_rate"] == 1e-3
    source = {"embed_dim": "hidden_size", "num_layers": "num_hidden_layers", "mlp_ch": "intermediate_size",
              "expert_ch": "moe_intermediate_size", "num_experts": "num_experts_published",
              "top_k": "num_experts_per_tok", "norm_eps": "norm_eps", "first_dense": "num_dense_layers_published",
              "routed_scale": "routed_scaling_factor", "tie_head": "tie_word_embeddings"}
    assert {k: registered[k] for k in source} == {k: config[v] for k, v in source.items()}
    assert list(registered["mixers"]) == config["layer_types"] and len(config["layer_types"]) == 40
    assert registered["rope_theta"] == config["rope_parameters"]["rope_theta"]
    head = config["hidden_size"] // config["num_attention_heads"]
    assert registered["gated_attention"] == {
        "num_heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"],
        "head_ch": head, "rotary_ch": head, "gate": False,
    }
    assert registered["short_conv"] == {"conv_width": config["conv_L_cache"]} and config["conv_bias"] is False
    assert registered["mtp_modules"] == 0 and registered["shared_expert"] is False and not registered.get("norm_offset")
    assert registered["scoring"] == "sigmoid" and config["use_expert_bias"] and config["norm_topk_prob"]
    assert registered["router_weight_eps"] == 1e-6


def test_the_cuts_parameters_are_the_files_arithmetic():
    """The tree the cell trains, counted from shapes alone: 469,284,992
    parameters, 7.51 GB of state at 16 bytes each."""
    import jax
    import jax.numpy as jnp

    from sav_tpu.models import create_model

    config = published()
    model = create_model(config["model_name"], num_classes=config["vocab_size"], dtype=jnp.bfloat16,
                         num_layers=config["num_layers"], **config["model_overrides"])
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = jax.eval_shape(lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))["params"]
    count = {k: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(v)) for k, v in tree.items()}
    assert count["layer_0"] == 89_139_200 and count["layer_1"] == 86_118_528
    assert count["layer_2"] == count["layer_3"] == count["layer_4"] == 92_416_000
    assert count["embed"] == 16_777_216 and count["final_norm"] == 2_048 and "lm_head" not in count
    assert sum(count.values()) == 469_284_992
    for number in ("469,284,992", "89,139,200", "92,416,000", "86,118,528", "16,777,216", "7.51 GB"):
        assert number in config["cut"]["arithmetic"]
    block = lambda layer, name: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree[layer][name]))
    assert block("layer_0", "ShortConvBlock_0") == 16_783_360
    assert block("layer_1", "GatedSelfAttentionBlock_0") == 10_485_888
    assert block("layer_0", "GatedFFBlock_0") == 72_351_744
    assert block("layer_2", "moe") == 8 * 9_437_184 + 131_072  # eight experts and the router: no shared expert
    assert 16 * 469_284_992 / 1e9 == pytest.approx(7.51, abs=0.005)
    whole = create_model(config["model_name"], num_classes=config["vocab_size_published"])
    tree = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))["params"]
    assert sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree)) == 23_843_659_008
    assert "23,843,659,008" in config["parameters_published"]


# ------------------------------------------------------------------- counts


def test_flops_against_a_hand_count():
    config = published()
    conv = 2048 * 6144 + 2048 * 2048  # multiply-adds a token: in_proj, out_proj
    assert conv == 16_783_360 - 3 * 2048  # the block's parameters less the kernel
    core = 2048 * (2 * 3 + 2)  # FLOP a token: a product, three multiply-adds, a product
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert attention == 10_485_888 - 128  # the block's parameters less the two norms
    pairs = 32 * 2 * 64 * 8193 / 2  # multiply-adds a position: (S + 1) / 2 visible pairs, logits and weighted sum
    dense, expert, router, head = 3 * 2048 * 11776, 3 * 2048 * 1536, 2048 * 64, 2048 * 8192
    routed = 0.5 * expert  # 4 a token x 8 of 64 held
    token = 4 * (2 * conv + core) + 2 * attention + 2 * pairs + 2 * dense + 4 * 2 * (router + routed) + 2 * head
    assert flops.forward_flops_per_image(config) == pytest.approx(8192 * token, rel=1e-12)
    assert token == pytest.approx(405.6e6, rel=1e-3) and 2 * dense == pytest.approx(144.7e6, rel=1e-3)
    assert flops.train_flops_per_image(config) * 4 == pytest.approx(39.87e12, rel=1e-3)  # a step of 4 sequences
    owners = flops.forward_flops_by_owner(config)
    share = {k: round(100 * v / sum(owners.values()), 1) for k, v in owners.items()}
    assert share == {"conv_projections": 33.1, "conv_core": 0.0, "attention_projections": 5.2, "attention_core": 8.3,
                     "dense_mlp": 35.7, "router": 0.3, "routed_experts": 9.3, "head": 8.3}
    assert flops.held_routings_per_token(config) == 0.5 and flops.head_dim(config) == 64
    assert flops.layer_kinds(config) == {"conv": 4, "full": 1, "dense": 1, "routed": 4}


def test_the_two_floors_against_a_hand_count():
    config = published()
    tokens = 4 * 8192
    # The core: B, C, x~ in and C * c out in bfloat16; backward those three and dy in, three gradients out.
    assert flops.short_conv_core_bytes_per_token(config) == {"forward": 4 * 2048 * 2, "backward": 7 * 2048 * 2}
    forward, backward = tokens * 4 * 2048 * 2 / 819e9, tokens * 7 * 2048 * 2 / 819e9
    assert tokens * 2048 * 8 / 197e12 < forward / 100  # the FLOPs are two orders under the bytes
    once = flops.short_conv_floor_seconds(config, tokens, False, 197e12, 819e9)
    assert once == pytest.approx(4 * (forward + backward)) and once == pytest.approx(7.21e-3, rel=1e-2)
    assert flops.short_conv_floor_seconds(config, tokens, True, 197e12, 819e9) == pytest.approx(
        4 * (2 * forward + backward))
    # The attention at the REAL head: 32 query heads of 64, 4 D S (S + 1) / 2 each, forward; 2.5 times that backward.
    pairs = 8192 * 8193 / 2
    floor = flops.attention_floor_seconds(config, 4, 197e12, 819e9)
    assert floor["forward"] == pytest.approx(4 * 32 * 4 * 64 * pairs / 197e12) and floor["forward_bound"] == "flops"
    assert floor["backward"] == pytest.approx(2.5 * floor["forward"]) and floor["backward_bound"] == "flops"
    assert floor["forward"] == pytest.approx(5.58e-3, rel=1e-2)
    assert flops.attention_forward_bytes(config) == 8192 * 64 * (2 * 32 + 2 * 8) * 2 + 4 * 8192 * 32
    assert flops.attention_backward_bytes(config) == 8192 * 64 * (4 * 32 + 4 * 8) * 2 + 4 * 8192 * 32
    # The grouped matmuls, at this family's keys.
    one = 2 * 16384 * 2048 * 1536
    assert flops.grouped_matmul_flops(config, 16384) == {"forward": 3 * one, "backward": 6 * one}
    kernels, rows = 3 * 8 * 2048 * 1536 * 2, 16384 * (2 * (2048 + 1536) + 1536 + 2048) * 2
    assert flops.grouped_matmul_floor_seconds(config, 16384, 197e12, 819e9)["forward"] == pytest.approx(
        max(3 * one / 197e12, (kernels + rows) / 819e9))


# ------------------------------------------------------------------ readers

STEP = "jit(_train_step_impl)/"
FWD = STEP + "jvp(JoyAILM)/"
BWD = STEP + "transpose(jvp(JoyAILM))/jvp(JoyAILM)/checkpoint/"
CONV, ATTN = "layer_2/ShortConvBlock_0/", "layer_1/GatedSelfAttentionBlock_0/"
SCOPES = {
    "in.1": FWD + CONV + "to_qkv/in_proj/dot_general",
    "core.2": FWD + CONV + "sconv/core/mul",
    "core.3": BWD + "rematted_computation/" + CONV + "sconv/core/mul",
    "core.4": BWD + CONV + "sconv/core/mul",
    "out.5": BWD + CONV + "to_out/out_proj/dot_general",
    "attn.6": FWD + ATTN + "pallas_call",
    "attn.7": BWD + ATTN + "pallas_call",
    "pad.8": FWD + ATTN + "pad",  # the head padded to 128 lanes: a copy, no Mosaic call
    "rep.9": FWD + ATTN + "broadcast_in_dim",  # k repeated to the query heads
    "qkv.10": FWD + ATTN + "to_qkv/q/dot_general",
    "gmm.11": FWD + "layer_2/moe/experts/fc1/jit(gmm)/pallas_call",
    "head.12": FWD + "lm_head/checkpoint/dot_general",
    "fusion.13": STEP + "optimizer/add",
    "mlp.14": FWD + "layer_0/GatedFFBlock_0/fc1/gate/dot_general",
}
OP_SECONDS = {"in.1": 6.0, "core.2": 1.0, "core.3": 1.0, "core.4": 2.0, "out.5": 2.0, "attn.6": 2.0, "attn.7": 5.0,
              "pad.8": 1.0, "rep.9": 2.0, "qkv.10": 3.0, "gmm.11": 2.0, "head.12": 4.0, "fusion.13": 1.0, "mlp.14": 8.0}
TOTAL = sum(OP_SECONDS.values())
IN_BLOCK, IN_CORE, ATTENTION_CORE = 12.0, 4.0, 10.0  # in, core x3, out | core x3 | two calls, pad, repeat


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
    """A program without the two blocks: the parent's, another family's."""
    return {k: v.replace("GatedSelfAttentionBlock", "LatentSelfAttentionBlock")
            for k, v in SCOPES.items() if "ShortConvBlock" not in v}


def test_short_conv_share_is_the_whole_block():
    read = harness.load_reader("model.short_conv_share")
    trace = {"op_seconds": OP_SECONDS}
    assert read(record(), trace) == pytest.approx(100 * IN_BLOCK / TOTAL)
    other = another_familys_scopes()
    assert read(record(hlo_scopes=other), {"op_seconds": {k: v for k, v in OP_SECONDS.items() if k in other}}) is None
    assert read(record(), None) is None and read(record(hlo_scopes=None), trace) is None


def test_short_conv_roofline_share_counts_the_cores_bytes_whoever_runs_it():
    read = harness.load_reader("kernel.short_conv_roofline_share")
    config, trace = published(), {"op_seconds": OP_SECONDS}
    least = 2 * flops.short_conv_floor_seconds(config, 4 * 8192, True, 197e12, 819e9)  # two traced steps, recomputed
    assert read(record(), trace) == pytest.approx(100 * least / IN_CORE)
    kept = {k: v.replace("rematted_computation/", "") for k, v in SCOPES.items()}
    once = 2 * flops.short_conv_floor_seconds(config, 4 * 8192, False, 197e12, 819e9)
    assert read(record(hlo_scopes=kept), trace) == pytest.approx(100 * once / IN_CORE)
    # Nothing to read: no such scope, another family's record, no chip, no trace.
    other = another_familys_scopes()
    assert read(record(hlo_scopes=other), {"op_seconds": {k: v for k, v in OP_SECONDS.items() if k in other}}) is None
    qwen = read_json(ROOT, "benchmark", "configs", "qwen3_next_80b_a3b.json")
    assert read(record(config=qwen), trace) is None  # a family whose file counts no such core
    assert read(record(config={}), trace) is None and read(record(hlo_scopes=None), trace) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), trace) is None
    assert read(record(), None) is None
    assert read({"spans": {}, "device": {"platform": "tpu"}}, trace) is None


def test_gqa64_roofline_share_holds_the_copies_and_counts_one_backward_a_call():
    read = harness.load_reader("kernel.gqa64_attention_roofline_share")
    config, trace = published(), {"op_seconds": OP_SECONDS}
    floor = flops.attention_floor_seconds(config, 4, 197e12, 819e9)
    # One forward and one backward call; the seconds are the calls' 2 + 5 AND the pad and the repeat, 1 + 2.
    assert read(record(), trace) == pytest.approx(100 * 2 * (floor["forward"] + floor["backward"]) / ATTENTION_CORE)
    # The forward recomputed under remat: two forward floors, still one backward.
    again = dict(SCOPES, **{"attn.15": BWD + "rematted_computation/" + ATTN + "pallas_call"})
    calls = {k: v for k, v in again.items() if v.endswith("pallas_call")}
    seconds = {"op_seconds": dict(OP_SECONDS, **{"attn.15": 2.0})}
    assert read(record(hlo_scopes=again, kernel_calls=calls), seconds) == pytest.approx(
        100 * 2 * (2 * floor["forward"] + floor["backward"]) / 12.0)
    # Two backward calls (the two-kernel form) are one application's backward between them.
    two = dict(SCOPES, **{"attn.16": BWD + ATTN + "pallas_call"})
    calls = {k: v for k, v in two.items() if v.endswith("pallas_call")}
    seconds = {"op_seconds": dict(OP_SECONDS, **{"attn.16": 3.0})}
    assert read(record(hlo_scopes=two, kernel_calls=calls), seconds) == pytest.approx(
        100 * 2 * (floor["forward"] + floor["backward"]) / 13.0)
    # Nothing to read: another family's calls, a head of whole lane tiles, another family's file, no chip, no trace.
    other = another_familys_scopes()
    calls = {k: v for k, v in other.items() if v.endswith("pallas_call")}
    assert read(record(hlo_scopes=other, kernel_calls=calls), trace) is None
    assert read(record(config={**config, "num_attention_heads": 16}), trace) is None  # heads of 128: another reader's
    qwen = read_json(ROOT, "benchmark", "configs", "qwen3_next_80b_a3b.json")
    assert read(record(config=qwen), trace) is None  # its file names no head_dim function
    assert read(record(config={}), trace) is None and read(record(kernel_calls=None), trace) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), trace) is None
    assert read(record(), None) is None


def test_the_accepted_readers_read_the_new_scopes():
    trace = {"op_seconds": OP_SECONDS}
    # The attention's core: the two calls and the copies around them; the conv block is no attention block.
    assert harness.load_reader("model.attention_share")(record(), trace) == pytest.approx(100 * 10.0 / TOTAL)
    assert harness.load_reader("kernel.attention_engaged_share")(record(), trace) == pytest.approx(100 * 7.0 / 10.0)
    # to_qkv and to_out of both blocks, the dense MLP and the grouped matmuls
    assert harness.load_reader("model.matmul_share")(record(), trace) == pytest.approx(100 * 21.0 / TOTAL)
    assert harness.load_reader("model.unowned_share")(record(), trace) == 0.0
    assert harness.load_reader("trainer.optimizer_share")(record(), trace) == pytest.approx(100 * 1.0 / TOTAL)
    assert harness.load_reader("model.recompute_share")(record(), trace) == pytest.approx(100 * 1.0 / TOTAL)
    assert harness.load_reader("model.mtp_share")(record(), trace) is None  # no module
    assert harness.load_reader("model.gdn_share")(record(), trace) is None  # no delta-rule block
    # The expert family's readers find this family's record as they find their own: its flops file answers
    # every function they look up, its configuration every key (a benchmark PR can list the cell by data alone).
    assert harness.load_reader("model.moe_routed_share")(record(), trace) == pytest.approx(100 * 2.0 / TOTAL)
    assert harness.load_reader("model.moe_dispatch_share")(record(), trace) == 0.0
    assert harness.load_reader("kernel.grouped_matmul_roofline_share")(record(), trace) is not None
    assert harness.load_reader("kernel.mla_attention_roofline_share")(record(), trace) is None  # no latent block
    assert harness.load_reader("kernel.gated_attention_roofline_share")(record(), trace) is None  # no head_dim key


# ------------------------------------------------------------------- schema


def test_benchmark_json_holds_the_cell_and_its_files(bench):
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "train_tokens_fit"
    assert (cell["config"]["name"], cell["traffic"]) == ("lfm2_24b_a2b", "train_ep8_conv_resident_8k")
    config = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b")
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert len(entry["source"]) <= 200 and entry["file"] == "benchmark/configs/lfm2_24b_a2b.json"
    assert entry["source"].startswith("https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert (config["num_layers"], config["num_dense_layers"], config["num_experts"], config["vocab_size"]) == (
        5, 1, 8, 8192)
    assert (config["num_layers_published"], config["num_dense_layers_published"], config["num_experts_published"],
            config["vocab_size_published"]) == (40, 2, 64, 65536)
    assert config["num_hidden_layers"] == 40 and config["expert_offset"] == 0
    for key in ("what", "deployment", "arithmetic", "compiled_step", "floors", "what_it_skews"):
        assert config["cut"][key]
    for key in ("tied_head", "sequence_length", "adam_b1_b2_eps", "peak_learning_rate", "weight_decay",
                "clip_grad_norm", "balance_term", "selection_bias", "weight_normalisation_eps", "documents",
                "precision", "conv_kernel_scale"):
        assert config["assumed"][key]
    assert set(cell["limits"]) >= {"first_grad_rel_diff", "update_rel_diff", "compiles_in_window"}
    for name, (better, layer) in NEW_METRICS.items():
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_img_s_chip"
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
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    config = published()
    differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
    # num_layers is a key of its own beside num_hidden_layers, which stays the published 40
    assert differs == set(REDUCED) - {"num_layers"}
    assert not [k for k in config["reduced"] if any(w in k for w in schema.WIDTH_WORDS)]
    assert config["source"].startswith(row["source_url"])
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "num_experts_per_tok", "conv_L_cache", "rope_parameters", "layer_types"):
        assert config[width] == row["config"][width]
