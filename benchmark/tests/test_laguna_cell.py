"""The Laguna cell's files: the token driver end to end at a toy size on the
CPU with the sliding-window / full attention hybrid family (sound run correct,
both controls not: the precision below, and the band left out), the cut's
parameter count from shapes and the published model's, the FLOP counts and the
two attention floors against a hand count, the four readers on a hand-made
table, the accepted listless readers on this family's record, and the catalog
row's keys against the configuration's file. Nothing here counts the
benchmark's cells or names the last entries of a list: the next cell changes
those."""

import copy
import json
import math
import os
import time

import pytest

from benchmark import run as harness, schema
from benchmark.flops import laguna as flops

from conftest import FIXTURES, ROOT, read_json

TOY = os.path.join(FIXTURES, "toy_laguna")
CELL = "laguna.train_ep32_4k"
NEW_METRICS = {
    "model.window_attention_share": ("lower", "models"),
    "model.full_attention_share": ("lower", "models"),
    "kernel.window_attention_roofline_share": ("higher", "kernels"),
    "kernel.full_attention_roofline_share": ("higher", "kernels"),
}
REDUCED = ["num_layers", "num_experts", "vocab_size"]


def published():
    return read_json(ROOT, "benchmark", "configs", "laguna_s_2.1.json")


@pytest.fixture()
def toy_cell():
    return {
        "name": "toy.laguna_train", "chips": 1,
        "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"),
        "limits": read_json(TOY, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_laguna", "source": "toy", "file": "benchmark/tests/fixtures/toy_laguna/config.json",
        "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_laguna", "traffic": "toy_tokens", "chips": 1, "why": "toy",
    })
    for metric in extended["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(toy_cell["name"])
    return extended


def band_left_out(config: dict) -> dict:
    """The second control as ``TrainConfig`` overrides: the model's arguments
    as the driver passes them, the window layers' sizes without their window."""
    sizes = config["model_overrides"] if "sliding_attention" in config["model_overrides"] else None
    if sizes is None:  # the published sizes are the registry's
        from sav_tpu.models.registry import _REGISTRY

        sizes = {"sliding_attention": _REGISTRY[config["model_name"]][1]["sliding_attention"]}
    sliding = {k: v for k, v in sizes["sliding_attention"].items() if k != "window"}
    return {"model_overrides": {
        "num_layers": config["num_layers"], "remat": config["train"]["remat"], **config["model_overrides"],
        "sliding_attention": sliding,
    }}


@pytest.mark.parametrize("arm", ["sound", "control", "band_left_out"])
def test_token_driver_carries_the_window_family_and_decides_correct(toy_bench, toy_cell, arm):
    overrides = {
        "sound": None,
        "control": read_json(TOY, "limits.json")["control"]["train_config"],
        "band_left_out": band_left_out(toy_cell["config"]),
    }[arm]
    line = harness.run_cell(toy_bench, toy_cell, 2**31 + 9, 0.3, False, process_t0=time.perf_counter(),
                            overrides=overrides)
    assert {r["check"] for r in line["checks"]} == set(toy_cell["limits"])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
    failed = [r["check"] for r in line["checks"] if not r["ok"]]
    if arm == "sound":
        assert line["correct"] is True, failed
    elif arm == "control":  # the toy states float32: bfloat16 is its precision below
        assert line["correct"] is False and {"first_grad_rel_diff", "update_rel_diff"} <= set(failed)
    else:  # the causal mask alone in the window layers: another model, at the seeded weights too
        assert line["correct"] is False and {"first_grad_rel_diff", "first_grad_norm_gap"} <= set(failed)


def test_the_band_left_out_control_builds_the_published_sizes_without_the_window():
    overrides = band_left_out(published())["model_overrides"]
    assert overrides["sliding_attention"]["num_heads"] == 72 and "window" not in overrides["sliding_attention"]
    assert overrides["experts_held"] == [0, 8] and overrides["num_layers"] == 5 and overrides["remat"] is True


def test_the_mix_the_recipe_and_the_registry_agree():
    config, mix = published(), read_json(ROOT, "benchmark", "traffic", "train_ep32_swa_resident_4k.json")
    assert mix["driver"] == "train_tokens_fit" and (mix["pool_batches"], mix["followed_steps"]) == (4, 3)
    assert (mix["warmup_log_windows"], mix["trace_log_windows"]) == (2, 4)
    train = mix["train_config"]
    assert set(train) == set(k for key in mix["train_config_why"] for k in key.replace(" (the mix's own key, beside train_config)", "").split(", ")) - {"warmup_log_windows"}
    assert train["aux_loss_weight"] == config["recipe"]["balance_alpha"] == 1e-3
    assert (train["log_every_steps"], train["warmup_epochs"], train["num_epochs"]) == (1, 0, 1)
    assert (train["base_lr"], train["end_lr"], train["weight_decay"], train["clip_grad_norm"]) == (3e-4, 1e-6, 0.1, 1.0)
    batch = config["train"]["per_chip_batch"]
    assert batch == 1 and (train["num_train_images"], train["lr_scaling_divisor"]) == (3 * batch, batch)
    assert config["recipe"]["entropy_weight"] == 0.0  # read by the driver, not by this family
    assert config["model_overrides"] == {"experts_held": [config["expert_offset"], config["num_experts"]]}
    assert config["first_k_dense_replace"] == len(config["mlp_only_layers"]) == 1 and config["num_nextn_predict_layers"] == 0
    assert config["train"]["remat"] is True
    assert (config["vocab_size"], config["num_layers"], config["sequence_length"]) == (12544, 5, 4096)
    assert config["vocab_size"] * 8 == config["vocab_size_published"] and config["num_experts"] * 32 == config["num_experts_published"]
    from sav_tpu.models.registry import _REGISTRY

    cls, registered = _REGISTRY["laguna_s_2.1"]
    source = {"embed_dim": "hidden_size", "num_layers": "num_hidden_layers", "mlp_ch": "intermediate_size",
              "expert_ch": "moe_intermediate_size", "num_experts": "num_experts_published",
              "top_k": "num_experts_per_tok", "norm_eps": "rms_norm_eps", "routed_scale": "moe_routed_scaling_factor"}
    assert {k: registered[k] for k in source} == {k: config[v] for k, v in source.items()}
    assert list(registered["mixers"]) == config["layer_types"]
    heads = {"full_attention": registered["gated_attention"]["num_heads"],
             "sliding_attention": registered["sliding_attention"]["num_heads"]}
    assert [heads[kind] for kind in config["layer_types"]] == config["num_attention_heads_per_layer"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47 and set(config["gating_types"]) == {"per_head"}
    assert registered["sliding_attention"]["window"] == config["sliding_window"] == 512
    rope = config["rope_parameters"]
    assert registered["sliding_attention"]["rope_theta"] == rope["sliding_attention"]["rope_theta"] == 10000
    full = registered["gated_attention"]
    assert full["rope_theta"] == rope["full_attention"]["rope_theta"] == 500000
    assert full["rotary_ch"] == config["head_dim"] * rope["full_attention"]["partial_rotary_factor"] == 64
    assert {k: v for k, v in full["rope_scaling"].items()} == {
        k: rope["full_attention"][k] for k in full["rope_scaling"]}
    assert config["shared_expert_intermediate_size"] == config["moe_intermediate_size"] and config["norm_topk_prob"]
    assert registered["mtp_modules"] == 0 and not registered.get("tie_head") and not config["tie_word_embeddings"]
    assert config["moe_router_logit_softcapping"] == 0 and not config["moe_apply_router_weight_on_input"]
    assert not config["attention_bias"] and config["decoder_sparse_step"] == 1


def test_the_cuts_parameters_are_the_files_arithmetic():
    """The tree the cell trains, counted from shapes alone: 811,030,784
    parameters (ISSUE 46's 810,995,712 and 35,072 of norms), 12.98 GB of state
    at 16 bytes each; and the published model's 117.56 B."""
    import jax
    import jax.numpy as jnp

    from sav_tpu.models import create_model

    config = published()
    model = create_model(config["model_name"], num_classes=config["vocab_size"], dtype=jnp.bfloat16,
                         num_layers=config["num_layers"], **config["model_overrides"])
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = jax.eval_shape(lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))["params"]
    count = {k: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(v)) for k, v in tree.items()}
    block = lambda layer, name: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree[layer][name]))
    attention = "GatedSelfAttentionBlock_0"
    assert block("layer_0", attention) == block("layer_4", attention) == 44_187_648 + 256
    assert block("layer_1", attention) == block("layer_2", attention) == block("layer_3", attention) == 63_135_744 + 256
    assert block("layer_0", "GatedFFBlock_0") == 113_246_208
    assert block("layer_1", "moe") == 9 * 9_437_184 + 786_432 + 3_072 == 85_724_160  # eight routed, one shared, router, gate
    assert count["layer_0"] == 44_187_904 + 113_246_208 + 6_144
    assert count["layer_1"] == count["layer_2"] == count["layer_3"] == 63_136_000 + 85_724_160 + 6_144
    assert count["layer_4"] == 44_187_904 + 85_724_160 + 6_144
    assert count["embed"] == count["lm_head"] == 12_544 * 3_072 == 38_535_168 and count["final_norm"] == 3_072
    norms = 10 * 3_072 + 3_072 + 10 * 128
    assert sum(count.values()) == 810_995_712 + norms == 811_030_784
    for number in ("810,995,712", "811,030,784", "44,187,648", "63,135,744", "113,246,208", "85,724,160",
                   "77,070,336", "12.98 GB", "35,072"):
        assert number in config["cut"]["arithmetic"], number
    assert 16 * 811_030_784 / 1e9 == pytest.approx(12.98, abs=0.005)
    assert 16 * 811_030_784 / 17.18e9 == pytest.approx(0.755, abs=0.001)
    whole = create_model(config["model_name"], num_classes=config["vocab_size_published"])
    tree = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))["params"]
    total = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))
    assert total == 117_562_109_952 and "117,562,109,952" in config["parameters_published"]
    layer = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree["layer_2"]["moe"]))
    assert layer == 257 * 9_437_184 + 786_432 + 3_072  # one published expert layer's FFN: 38.8 GB of state
    assert 16 * 257 * 9_437_184 / 1e9 == pytest.approx(38.8, abs=0.05)


# ------------------------------------------------------------------- counts


def test_flops_against_a_hand_count():
    config = published()
    assert flops.layer_kinds(config) == {"window": 3, "full": 2, "dense": 1, "routed": 4}
    assert flops.layer_kinds({**config, "num_layers": 48}) == {"window": 36, "full": 12, "dense": 1, "routed": 47}
    assert (flops.heads_of(config, "window"), flops.heads_of(config, "full")) == (72, 48)
    # Multiply-adds a token: q, k, v, o and the head-wise gate.
    window = 3072 * 9216 + 2 * 3072 * 1024 + 9216 * 3072 + 3072 * 72
    full = 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 + 3072 * 48
    assert (window, full) == (63_135_744, 44_187_648)  # the blocks' parameters less their norms
    band = sum(min(i + 1, 512) for i in range(4096))
    triangle = 4096 * 4097 // 2
    assert (band, triangle) == (1_966_336, 8_390_656)
    assert flops.visible_pairs(config, "window") == band and flops.visible_pairs(config, "full") == triangle
    assert flops.visible_pairs({**config, "sequence_length": 256}, "window") == 256 * 257 // 2  # a window past the sequence
    assert flops.attention_forward_flops(config, "window") == 4 * 128 * 72 * band  # ISSUE 46: 4 x 128 x H_l x sum_i min(i + 1, 512)
    assert flops.attention_forward_flops(config, "full") == 4 * 128 * 48 * triangle
    dense, expert, router, head = 3 * 3072 * 12288, 3 * 3072 * 1024, 3072 * 256, 3072 * 12544
    routed = 0.3125 * expert  # 10 a token x 8 of 256 held
    core = (3 * 72 * 2 * 128 * band + 2 * 48 * 2 * 128 * triangle) / 4096  # multiply-adds a token
    token = 3 * window + 2 * full + core + dense + 4 * (router + expert + 3072 + routed) + head
    assert flops.forward_flops_per_image(config) == pytest.approx(4096 * 2 * token, rel=1e-12)
    assert flops.train_flops_per_image(config) == pytest.approx(13.74e12, rel=1e-3)  # a step of one sequence
    owners = flops.forward_flops_by_owner(config)
    share = {k: round(100 * v / sum(owners.values()), 1) for k, v in owners.items()}
    assert share == {"window_projections": 33.9, "window_core": 4.7, "full_projections": 15.8, "full_core": 9.0,
                     "dense_mlp": 20.3, "router": 0.6, "shared_experts": 6.8, "routed_experts": 2.1, "head": 6.9}
    assert flops.held_routings_per_token(config) == 0.3125


def test_the_two_floors_against_a_hand_count():
    config = published()
    band, triangle = 1_966_336, 8_390_656
    window = flops.window_attention_floor_seconds(config, 1, 197e12, 819e9)
    full = flops.full_attention_floor_seconds(config, 1, 197e12, 819e9)
    assert window["forward"] == pytest.approx(72 * 4 * 128 * band / 197e12) and window["forward_bound"] == "flops"
    assert window["backward"] == pytest.approx(2.5 * 72 * 4 * 128 * band / 197e12) and window["backward_bound"] == "flops"
    assert full["forward"] == pytest.approx(48 * 4 * 128 * triangle / 197e12) and full["backward_bound"] == "flops"
    assert window["forward"] == pytest.approx(0.368e-3, rel=1e-2) and full["forward"] == pytest.approx(1.047e-3, rel=1e-2)
    # The band's floor counts the band's work: a quarter of what the triangle would give those heads.
    assert window["forward"] / (72 * 4 * 128 * triangle / 197e12) == pytest.approx(band / triangle) == pytest.approx(0.2343, abs=1e-4)
    # Bytes: q and o at the query heads, k and v at the key/value heads, the logsumexp; a slow chip is bound by them.
    forward_bytes = 4096 * 128 * (2 * 72 + 2 * 8) * 2 + 4 * 4096 * 72
    assert flops.attention_forward_bytes(config, "window") == forward_bytes
    assert flops.attention_backward_bytes(config, "full") == 4096 * 128 * (4 * 48 + 4 * 8) * 2 + 4 * 4096 * 48
    slow = flops.window_attention_floor_seconds(config, 2, 1e18, 819e9)
    assert slow["forward"] == pytest.approx(2 * forward_bytes / 819e9) and slow["forward_bound"] == "bytes"
    # The grouped matmuls, at this family's keys (the accepted expert readers look these names up).
    one = 2 * 1024 * 3072 * 1024
    assert flops.grouped_matmul_flops(config, 1024) == {"forward": 3 * one, "backward": 6 * one}
    kernels, rows = 3 * 8 * 3072 * 1024 * 2, 1024 * (2 * (3072 + 1024) + 1024 + 3072) * 2
    got = flops.grouped_matmul_floor_seconds(config, 1024, 197e12, 819e9)
    assert got["forward"] == pytest.approx(max(3 * one / 197e12, (kernels + rows) / 819e9)) and got["forward_bound"] == "bytes"


# ------------------------------------------------------------------ readers

STEP = "jit(_train_step_impl)/"
FWD = STEP + "jvp(JoyAILM)/"
BWD = STEP + "transpose(jvp(JoyAILM))/jvp(JoyAILM)/checkpoint/"
WINDOW, FULL, MOE = "layer_2/GatedSelfAttentionBlock_0/", "layer_4/GatedSelfAttentionBlock_0/", "layer_2/moe/"
SCOPES = {
    "q.1": FWD + WINDOW + "to_qkv/q/dot_general",
    "band.2": FWD + WINDOW + "attn/window/pallas_call",
    "copy.3": FWD + WINDOW + "attn/window/transpose",
    "band.4": BWD + "rematted_computation/" + WINDOW + "attn/window/pallas_call",  # a forward computed again
    "band.5": BWD + WINDOW + "attn/window/pallas_call",  # the one-kernel backward
    "sum.6": BWD + WINDOW + "attn/window/reduce_sum",  # a group's dk and dv summed after the call
    "gate.7": FWD + WINDOW + "mul",
    "out.8": BWD + WINDOW + "to_out/dot_general",
    "full.9": FWD + FULL + "attn/full/pallas_call",
    "full.10": BWD + FULL + "attn/full/pallas_call",
    "full.11": BWD + "rematted_computation/" + FULL + "attn/full/pallas_call",
    "qkv.12": FWD + FULL + "to_qkv/gate/dot_general",
    "route.13": FWD + MOE + "route/dot_general",
    "sort.14": FWD + MOE + "dispatch/sort",
    "gmm.15": FWD + MOE + "experts/fc1/jit(gmm)/pallas_call",
    "shared.16": FWD + MOE + "shared/fc1/gate/dot_general",
    "head.17": FWD + "lm_head/checkpoint/dot_general",
    "fusion.18": STEP + "optimizer/add",
    "mlp.19": FWD + "layer_0/GatedFFBlock_0/fc1/gate/dot_general",
    "while.20": FWD + MOE + "overflow/while",  # a loop's own event: spans its body
    "body.21": FWD + MOE + "overflow/while/body/moe/experts/fc1/jit(gmm)/pallas_call",
}
OP_SECONDS = {"q.1": 6.0, "band.2": 1.0, "copy.3": 0.5, "band.4": 1.0, "band.5": 2.5, "sum.6": 0.5, "gate.7": 1.0,
              "out.8": 2.0, "full.9": 2.0, "full.10": 5.0, "full.11": 2.0, "qkv.12": 3.0, "route.13": 1.0,
              "sort.14": 2.0, "gmm.15": 2.0, "shared.16": 3.0, "head.17": 4.0, "fusion.18": 1.0, "mlp.19": 8.0,
              "while.20": 1.5, "body.21": 1.0}
ONCE = sum(v for k, v in OP_SECONDS.items() if not k.startswith("while"))  # 48.5: the loop's own event left out
IN_WINDOW, IN_FULL = 5.5, 9.0  # the three calls, the copy and the group's sum | the three calls
WINDOW_CALLS, FULL_CALLS = 4.5, 9.0  # the Mosaic calls alone


def record(**over):
    base = {
        "hlo_scopes": SCOPES,
        "kernel_calls": {k: v for k, v in SCOPES.items() if v.endswith("pallas_call")},
        "config": published(),
        "spans": {"traced_steps": 2},
        "counters": {"images_per_step_per_chip": 1},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
    }
    return {**base, **over}


def without_the_scopes():
    """A program that does not name the two kinds (the parent's, another family's): the cores lie
    straight under the block."""
    return {k: v.replace("attn/window/", "").replace("attn/full/", "") for k, v in SCOPES.items()}


@pytest.mark.parametrize("name,ours", [("model.window_attention_share", IN_WINDOW), ("model.full_attention_share", IN_FULL)])
def test_the_two_shares_are_what_lies_under_each_scope_with_a_loop_counted_once(name, ours):
    read = harness.load_reader(name)
    trace = {"op_seconds": OP_SECONDS}
    assert ONCE == 48.5 and read(record(), trace) == pytest.approx(100 * ours / ONCE)
    assert read(record(hlo_scopes=without_the_scopes()), trace) is None  # the parent, another family
    assert read(record(), None) is None and read(record(hlo_scopes=None), trace) is None


def test_the_two_roofline_shares_count_each_kinds_calls_against_its_own_floor():
    config, trace = published(), {"op_seconds": OP_SECONDS}
    window = flops.window_attention_floor_seconds(config, 1, 197e12, 819e9)
    full = flops.full_attention_floor_seconds(config, 1, 197e12, 819e9)
    read = harness.load_reader("kernel.window_attention_roofline_share")
    # Two traced steps; a kind's calls here: a forward, a forward computed again, one backward.
    least = 2 * (2 * window["forward"] + window["backward"])
    assert read(record(), trace) == pytest.approx(100 * least / WINDOW_CALLS)
    read_full = harness.load_reader("kernel.full_attention_roofline_share")
    least_full = 2 * (2 * full["forward"] + full["backward"])
    assert read_full(record(), trace) == pytest.approx(100 * least_full / FULL_CALLS)
    # The band's floor is the band's: the same seconds against the triangle's floor would read 4.3 times higher.
    triangle = {**config, "sliding_window": 4096}
    assert read(record(config=triangle), trace) / read(record(), trace) == pytest.approx(8_390_656 / 1_966_336)
    # On the chip's peaks neither may pass 100: a call at its floor reads 100.
    at_floor = {**OP_SECONDS, "band.2": 2 * window["forward"], "band.4": 2 * window["forward"], "band.5": 2 * window["backward"]}
    assert read(record(), {"op_seconds": at_floor}) == pytest.approx(100.0)
    # Nothing to read: no such scope (the parent), another family's file, no chip, no trace, no calls.
    bare = without_the_scopes()
    assert read(record(hlo_scopes=bare, kernel_calls={k: v for k, v in bare.items() if v.endswith("pallas_call")}), trace) is None
    qwen = read_json(ROOT, "benchmark", "configs", "qwen3_next_80b_a3b.json")
    assert read(record(config=qwen), trace) is None and read_full(record(config=qwen), trace) is None
    assert read(record(config={}), trace) is None and read(record(kernel_calls=None), trace) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), trace) is None
    assert read(record(), None) is None
    assert read({"spans": {}, "device": {"platform": "tpu"}}, trace) is None


def test_the_accepted_listless_readers_read_this_familys_record():
    trace = {"op_seconds": OP_SECONDS}
    total = sum(OP_SECONDS.values())  # the accepted readers sum a loop's event with its body (ROADMAP B5)
    # Both kinds' cores (and the gate's product, which lies in the block outside its projections).
    assert harness.load_reader("model.attention_share")(record(), trace) == pytest.approx(100 * 15.5 / total)
    assert harness.load_reader("kernel.attention_engaged_share")(record(), trace) == pytest.approx(100 * 13.5 / 15.5)
    assert harness.load_reader("model.unowned_share")(record(), trace) == 0.0
    assert harness.load_reader("trainer.optimizer_share")(record(), trace) == pytest.approx(100 * 1.0 / total)
    assert harness.load_reader("model.kda_share")(record(), trace) is None
    assert harness.load_reader("model.gdn_share")(record(), trace) is None
    assert harness.load_reader("model.short_conv_share")(record(), trace) is None
    # The expert family's readers find this family's record as they find their own: its flops file answers
    # every function they look up, its configuration every key (a benchmark PR can list the cell by data alone).
    assert harness.load_reader("model.moe_routed_share")(record(), trace) is not None
    assert harness.load_reader("model.moe_dispatch_share")(record(), trace) is not None
    assert harness.load_reader("kernel.grouped_matmul_roofline_share")(record(), trace) is not None
    assert harness.load_reader("model.recompute_share")(record(), trace) == pytest.approx(100 * 3.0 / total)  # the two forwards computed again


# ------------------------------------------------------------------- schema


def test_benchmark_json_holds_the_cell_and_its_files(bench):
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "train_tokens_fit"
    assert (cell["config"]["name"], cell["traffic"]) == ("laguna_s_2.1", "train_ep32_swa_resident_4k")
    config = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "laguna_s_2.1")
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert len(entry["source"]) <= 200 and entry["file"] == "benchmark/configs/laguna_s_2.1.json"
    assert entry["source"].startswith("https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    assert (config["num_layers"], config["num_experts"], config["vocab_size"]) == (5, 8, 12544)
    assert (config["num_layers_published"], config["num_experts_published"], config["vocab_size_published"]) == (
        48, 256, 100352)
    assert config["num_hidden_layers"] == 48 and config["expert_offset"] == 0 and config["mlp_only_layers"] == [0]
    for key in ("what", "deployment", "arithmetic", "compiled_step", "what_it_skews"):
        assert config["cut"][key]
    for key in ("qk_norm", "hidden_act", "router_activation", "shared_expert_gate", "balance_term", "recipe"):
        assert config["assumed"][key], key
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
    schema.check(bench)


def test_every_key_of_the_catalog_row_is_in_the_file_or_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
    config = published()
    differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
    # num_layers is a key of its own beside num_hidden_layers, which stays the published 48
    assert differs == set(REDUCED) - {"num_layers"}
    assert not [k for k in config["reduced"] if any(w in k for w in schema.WIDTH_WORDS)]
    assert config["source"].startswith(row["source_url"])
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok", "sliding_window",
                  "rope_parameters", "layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer",
                  "moe_routed_scaling_factor", "mlp_only_layers"):
        assert config[width] == row["config"][width]
