"""The Ling-3.0-flash cell's files: the token driver end to end at a toy size
on the CPU with the vector-decay / latent-attention hybrid family (sound run
correct, the control not), the cut's parameter count from shapes and the
published model's, the FLOP counts and the floors against a hand count, the
three readers on a hand-made table, the accepted readers on this family's
record, and the catalog row's keys against the configuration's file. Nothing
here counts the benchmark's cells or names the last entries of a list: the
next cell changes those."""

import copy
import json
import math
import os
import time

import pytest

from benchmark import run as harness, schema
from benchmark.flops import ling as flops

from conftest import FIXTURES, ROOT, read_json

TOY = os.path.join(FIXTURES, "toy_ling")
CELL = "ling.train_ep64_4k"
NEW_METRICS = {
    "model.kda_share": ("lower", "models"),
    "kernel.kda_roofline_share": ("higher", "kernels"),
    "model.moe_route_share": ("lower", "models"),
}
REDUCED = ["num_layers", "first_k_dense_replace", "num_experts", "vocab_size"]


def published():
    return read_json(ROOT, "benchmark", "configs", "ling_3.0_flash.json")


@pytest.fixture()
def toy_cell():
    return {
        "name": "toy.ling_train", "chips": 1,
        "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"),
        "limits": read_json(TOY, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_ling", "source": "toy", "file": "benchmark/tests/fixtures/toy_ling/config.json",
        "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_ling", "traffic": "toy_tokens", "chips": 1, "why": "toy",
    })
    for metric in extended["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(toy_cell["name"])
    return extended


@pytest.mark.parametrize("arm", ["sound", "control"])
def test_token_driver_carries_the_kda_family_and_decides_correct(toy_bench, toy_cell, arm):
    control = read_json(TOY, "limits.json")["control"]["train_config"]
    line = harness.run_cell(toy_bench, toy_cell, 2**31 + 9, 0.3, False, process_t0=time.perf_counter(),
                            overrides=control if arm == "control" else None)
    assert {r["check"] for r in line["checks"]} == set(toy_cell["limits"])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_img_s_chip", "setup_s"}
    failed = [r["check"] for r in line["checks"] if not r["ok"]]
    if arm == "sound":
        assert line["correct"] is True, failed
    else:  # the toy states float32: bfloat16 is its precision below (the fixture's limits file has why)
        assert line["correct"] is False and {"first_grad_norm_gap", "first_grad_rel_diff", "update_rel_diff"} <= set(failed)


def test_the_mix_the_recipe_and_the_registry_agree():
    config, mix = published(), read_json(ROOT, "benchmark", "traffic", "train_ep64_kda_resident_4k.json")
    assert mix["driver"] == "train_tokens_fit" and (mix["pool_batches"], mix["followed_steps"]) == (4, 3)
    assert (mix["warmup_log_windows"], mix["trace_log_windows"]) == (2, 4)
    train = mix["train_config"]
    assert set(train) == set(k for key in mix["train_config_why"] for k in key.replace(" (the mix's own key, beside train_config)", "").split(", ")) - {"warmup_log_windows"}
    assert train["aux_loss_weight"] == config["recipe"]["balance_alpha"] == 1e-4
    assert (train["log_every_steps"], train["warmup_epochs"], train["num_epochs"]) == (1, 0, 1)
    assert (train["base_lr"], train["end_lr"], train["weight_decay"], train["clip_grad_norm"]) == (3e-4, 1e-6, 0.1, 1.0)
    # ISSUE 43's mix: two sequences a step, or its stated fall-back with the batch's numbers.
    batch = config["train"]["per_chip_batch"]
    assert batch in (2, 1) and (train["num_train_images"], train["lr_scaling_divisor"]) == (3 * batch, batch)
    assert config["recipe"]["entropy_weight"] == 0.0  # read by the driver, not by this family
    assert config["model_overrides"] == {
        "experts_held": [config["expert_offset"], config["num_experts"]], "first_dense": config["first_k_dense_replace"],
    }
    assert config["first_k_dense_replace"] == 1 and config["num_nextn_predict_layers"] == 0
    assert config["train"]["remat"] is True
    assert (config["vocab_size"], config["num_layers"], config["sequence_length"]) == (19648, 6, 4096)
    assert config["vocab_size"] * 8 == config["vocab_size_published"] and config["num_experts"] * 64 == config["num_experts_published"]
    from sav_tpu.models.registry import _REGISTRY

    cls, registered = _REGISTRY["ling_3.0_flash"]
    assert registered["bias_update_rate"] == config["recipe"]["bias_update_rate"] == 1e-3
    source = {"embed_dim": "hidden_size", "num_layers": "num_hidden_layers", "mlp_ch": "intermediate_size",
              "expert_ch": "moe_intermediate_size", "num_experts": "num_experts_published",
              "top_k": "num_experts_per_tok", "norm_eps": "rms_norm_eps", "first_dense": "first_k_dense_replace_published",
              "routed_scale": "routed_scaling_factor", "n_group": "n_group", "topk_group": "topk_group",
              "num_heads": "num_attention_heads", "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank",
              "nope_ch": "qk_nope_head_dim", "rope_ch": "qk_rope_head_dim", "v_ch": "v_head_dim",
              "rope_theta": "rope_theta", "latent_qk_norm": "use_qk_norm", "scoring": "score_function"}
    assert {k: registered[k] for k in source} == {k: config[v] for k, v in source.items()}
    assert registered["latent_gate"] is True and config["gated_attention_proj_granularity_type"] == "head_wise"
    assert registered["q_rank"] is None and config["moe_shared_expert_intermediate_size"] == config["moe_intermediate_size"]
    assert list(registered["expert_limits"]) == config["expert_swiglu_limit_list"]
    assert list(registered["shared_limits"]) == config["share_expert_swiglu_limit_list"]
    assert len(registered["mixers"]) == 42 and all(
        (kind == "latent") == ((i + 1) % config["layer_group_size"] == 0) for i, kind in enumerate(registered["mixers"]))
    assert registered["kda"] == {
        "heads": config["num_attention_heads"], "key_ch": config["head_dim"], "value_ch": config["head_dim"],
        "conv_width": config["short_conv_kernel_size"], "lower_bound": float(config["kda_lower_bound"]),
    }
    assert config["kda_safe_gate"] and config["no_kda_lora"] and not config["use_kda_lora"] and config["linear_silu"]
    assert registered["mtp_modules"] == 0 and not registered.get("tie_head") and not config["tie_word_embeddings"]
    assert config["moe_router_enable_expert_bias"] and config["norm_topk_prob"]
    assert config["rotary_dim"] == config["qk_rope_head_dim"] == config["partial_rotary_factor"] * config["head_dim"]


def test_the_cuts_parameters_are_the_files_arithmetic():
    """The tree the cell trains, counted from shapes alone: 767,006,752
    parameters (ISSUE 43's 767,006,496 and the 256 weights of use_qk_norm),
    12.27 GB of state at 16 bytes each; and the published model's."""
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
    assert block("layer_0", "KDABlock_0") == 63_049_888 and block("layer_4", "KDABlock_0") == 63_049_888
    assert block("layer_5", "LatentSelfAttentionBlock_0") == 31_965_696 + 256
    assert block("layer_0", "GatedFFBlock_0") == 47_185_920
    assert block("layer_1", "moe") == 9 * 5_898_240 + 1_310_720 == 54_394_880  # eight routed, one shared, the router
    assert count["layer_0"] == 63_049_888 + 47_185_920 + 5_120
    assert count["layer_1"] == count["layer_2"] == count["layer_3"] == count["layer_4"] == 63_049_888 + 54_394_880 + 5_120
    assert count["layer_5"] == 31_965_952 + 54_394_880 + 5_120
    assert count["embed"] == count["lm_head"] == 19_648 * 2_560 and count["final_norm"] == 2_560
    assert sum(count.values()) == 767_006_496 + 256
    for number in ("767,006,496", "767,006,752", "63,049,888", "31,965,696", "47,185,920", "54,394,880",
                   "100,600,320", "12.27 GB", "884,456,384", "1,002,936,096", "3,090,162,848"):
        assert number in config["cut"]["arithmetic"], number
    assert 16 * 767_006_752 / 1e9 == pytest.approx(12.27, abs=0.005)
    assert 767_006_496 + 63_049_888 + 54_394_880 + 5_120 == 884_456_384  # a seventh layer
    assert 767_006_496 + 5 * 8 * 5_898_240 == 1_002_936_096  # sixteen experts held
    whole = create_model(config["model_name"], num_classes=config["vocab_size_published"])
    tree = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))["params"]
    total = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))
    assert total == 124_414_191_072 + 7 * 256 == 124_414_192_864
    assert "124,414,192,864" in config["parameters_published"] and "124,414,191,072" in config["parameters_published"]
    layer = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree["layer_2"]))
    assert layer == 3_090_162_848  # one published KDA expert layer: 49.4 GB of state


# ------------------------------------------------------------------- counts


def test_flops_against_a_hand_count():
    config = published()
    kda = 5 * 2560 * 4096 + 2560 * 32 + 4096 * 2560  # multiply-adds a token: q, k, v, f, g; b; the merge
    assert kda == 63_049_888 - (3 * 4 * 4096 + 32 + 4096 + 128)  # the block's parameters less conv, A_log, dt_bias, norm
    conv = 3 * 4096 * 4
    rule = 32 * (2 * 64 * 128 + 2 * 64 * 128 + 3 * 128 * 128)  # the two pair terms, T and the masked product, three on the state
    assert 2 * rule == 32 * flops.kda_rule_flops_per_token_and_head(config) == 32 * 163_840
    latent = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    assert latent == 31_965_696 - 512  # the block's parameters less the latent's norm (and the 256 beside them)
    pairs = 32 * (192 + 128) * 4097 / 2  # multiply-adds a position: (S + 1) / 2 visible pairs, logits and weighted sum
    dense, expert, router, head = 3 * 2560 * 6144, 3 * 2560 * 768, 2560 * 512, 2560 * 19648
    routed = 0.125 * expert  # 8 a token x 8 of 512 held
    token = 5 * (kda + conv + rule) + latent + pairs + dense + 5 * (router + expert + routed) + head
    assert flops.forward_flops_per_image(config) == pytest.approx(4096 * 2 * token, rel=1e-12)
    assert token == pytest.approx(518.5e6, rel=1e-3) and 5 * kda == pytest.approx(315.0e6, rel=1e-3)
    assert flops.train_flops_per_image(config) * 2 == pytest.approx(25.48e12, rel=1e-3)  # a step of 2 sequences
    owners = flops.forward_flops_by_owner(config)
    share = {k: round(100 * v / sum(owners.values()), 1) for k, v in owners.items()}
    assert share == {"kda_projections": 60.7, "kda_conv": 0.0, "kda_rule": 2.5, "latent_projections": 6.2,
                     "attention_core": 4.0, "dense_mlp": 9.1, "router": 1.3, "shared_experts": 5.7,
                     "routed_experts": 0.7, "head": 9.7}
    assert flops.held_routings_per_token(config) == 0.125
    assert flops.layer_kinds(config) == {"latent": 1, "kda": 5}
    assert flops.layer_kinds({**config, "num_layers": 42}) == {"latent": 7, "kda": 35}


def test_the_floors_against_a_hand_count():
    config = published()
    tokens = 2 * 4096
    # The rule: q, k, v in and o out in bfloat16, g a float32 a KEY LANE, beta a float32 a head.
    assert flops.kda_rule_bytes_per_token(config) == 4 * 4096 * 2 + 4096 * 4 + 32 * 4 == 49_280
    by_bytes, by_flops = tokens * 49_280 / 819e9, tokens * 32 * 163_840 / 197e12
    assert by_flops < by_bytes  # a pass is bound by its bytes: 60 ns a token against 27
    once = flops.kda_rule_floor_seconds(config, tokens, False, 197e12, 819e9)
    assert once == pytest.approx(5 * 3 * by_bytes) and once == pytest.approx(7.39e-3, rel=1e-2)
    assert flops.kda_rule_floor_seconds(config, tokens, True, 197e12, 819e9) == pytest.approx(5 * 4 * by_bytes)
    assert flops.kda_rule_floor_seconds(config, tokens, True, 1e9, 819e9) == pytest.approx(5 * 4 * tokens * 32 * 163_840 / 1e9)
    # The latent core at the two head sizes: 2 (192 + 128) S (S + 1) / 2 a head forward.
    pairs = 4096 * 4097 / 2
    floor = flops.attention_floor_seconds(config, 2, 197e12, 819e9)
    assert floor["forward"] == pytest.approx(2 * 32 * 2 * 320 * pairs / 197e12) and floor["forward_bound"] == "flops"
    assert floor["backward"] == pytest.approx(2 * 32 * 2 * (3 * 192 + 2 * 128) * pairs / 197e12)
    assert floor["forward"] == pytest.approx(1.745e-3, rel=1e-2) and floor["backward_bound"] == "flops"
    joyai = read_json(ROOT, "benchmark", "configs", "joyai_llm_flash.json")
    from benchmark.flops import joyai as joyai_flops
    assert floor == joyai_flops.attention_floor_seconds(joyai, 2, 197e12, 819e9)  # JoyAI's kernel shape, its floor
    # The grouped matmuls, at this family's keys.
    one = 2 * 1024 * 2560 * 768
    assert flops.grouped_matmul_flops(config, 1024) == {"forward": 3 * one, "backward": 6 * one}
    kernels, rows = 3 * 8 * 2560 * 768 * 2, 1024 * (2 * (2560 + 768) + 768 + 2560) * 2
    got = flops.grouped_matmul_floor_seconds(config, 1024, 197e12, 819e9)
    assert got["forward"] == pytest.approx(max(3 * one / 197e12, (kernels + rows) / 819e9)) and got["forward_bound"] == "bytes"


# ------------------------------------------------------------------ readers

STEP = "jit(_train_step_impl)/"
FWD = STEP + "jvp(JoyAILM)/"
BWD = STEP + "transpose(jvp(JoyAILM))/jvp(JoyAILM)/checkpoint/"
KDA, LATENT, MOE = "layer_2/KDABlock_0/", "layer_5/LatentSelfAttentionBlock_0/", "layer_2/moe/"
SCOPES = {
    "q.1": FWD + KDA + "to_qkv/q/dot_general",
    "conv.2": FWD + KDA + "kda/conv/conv/pallas_call",
    "rule.3": FWD + KDA + "kda/rule/checkpoint/dot_general",
    "while.4": FWD + KDA + "kda/rule/while",  # the scan's loop event: spans its body
    "body.5": FWD + KDA + "kda/rule/while/body/checkpoint/dot_general",
    "rule.6": BWD + "rematted_computation/" + KDA + "kda/rule/checkpoint/dot_general",
    "rule.7": BWD + KDA + "kda/rule/transpose(checkpoint)/dot_general",
    "norm.8": FWD + KDA + "kda/gate_norm/gate_norm/checkpoint/mul",
    "out.9": BWD + KDA + "to_out/dot_general",
    "attn.10": FWD + LATENT + "pallas_call",
    "attn.11": BWD + LATENT + "pallas_call",
    "qkv.12": FWD + LATENT + "to_qkv/q/dot_general",
    "route.13": FWD + MOE + "route/dot_general",
    "groups.14": FWD + MOE + "route/groups/top_k",
    "route.15": BWD + MOE + "route/transpose(jvp(route))/dot_general",
    "sort.16": FWD + MOE + "dispatch/sort",
    "gmm.17": FWD + MOE + "experts/fc1/jit(gmm)/pallas_call",
    "shared.18": FWD + MOE + "shared/fc1/gate/dot_general",
    "head.19": FWD + "lm_head/checkpoint/dot_general",
    "fusion.20": STEP + "optimizer/add",
    "mlp.21": FWD + "layer_0/GatedFFBlock_0/fc1/gate/dot_general",
    "conditional.22": FWD + MOE + "overflow/cond",
}
OP_SECONDS = {"q.1": 6.0, "conv.2": 1.0, "rule.3": 2.0, "while.4": 3.5, "body.5": 3.0, "rule.6": 2.0, "rule.7": 5.0,
              "norm.8": 1.0, "out.9": 2.0, "attn.10": 2.0, "attn.11": 5.0, "qkv.12": 3.0, "route.13": 1.0,
              "groups.14": 2.0, "route.15": 1.0, "sort.16": 2.0, "gmm.17": 2.0, "shared.18": 3.0, "head.19": 4.0,
              "fusion.20": 1.0, "mlp.21": 8.0, "conditional.22": 0.5}
ONCE = sum(v for k, v in OP_SECONDS.items() if not k.startswith(("while", "conditional")))  # 56: loop events left out
IN_BLOCK, IN_RULE, IN_ROUTE = 22.0, 12.0, 4.0  # q, conv, rule x4 with the body, norm, out | rule x3, body | route x3


def record(**over):
    base = {
        "hlo_scopes": SCOPES,
        "kernel_calls": {k: v for k, v in SCOPES.items() if v.endswith("pallas_call")},
        "config": published(),
        "spans": {"traced_steps": 2},
        "counters": {"images_per_step_per_chip": 2},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
    }
    return {**base, **over}


def another_familys_scopes():
    """A program without the block and without the group step: the parent's, another family's."""
    return {k: v for k, v in SCOPES.items() if "KDABlock" not in v and "groups" not in v}


def test_kda_share_is_the_whole_block_with_a_loop_counted_once():
    read = harness.load_reader("model.kda_share")
    trace = {"op_seconds": OP_SECONDS}
    assert ONCE == 56.0 and read(record(), trace) == pytest.approx(100 * IN_BLOCK / ONCE)
    other = another_familys_scopes()
    assert read(record(hlo_scopes=other), {"op_seconds": {k: v for k, v in OP_SECONDS.items() if k in other}}) is None
    assert read(record(), None) is None and read(record(hlo_scopes=None), trace) is None


def test_kda_roofline_share_counts_the_rule_whoever_runs_it_and_a_scan_by_its_body():
    read = harness.load_reader("kernel.kda_roofline_share")
    config, trace = published(), {"op_seconds": OP_SECONDS}
    least = 2 * flops.kda_rule_floor_seconds(config, 2 * 4096, True, 197e12, 819e9)  # two traced steps, recomputed
    assert read(record(), trace) == pytest.approx(100 * least / IN_RULE)  # 12 s: the loop's own 3.5 are not in it
    kept = {k: v.replace("rematted_computation/", "") for k, v in SCOPES.items()}
    once = 2 * flops.kda_rule_floor_seconds(config, 2 * 4096, False, 197e12, 819e9)
    assert read(record(hlo_scopes=kept), trace) == pytest.approx(100 * once / IN_RULE)
    # Nothing to read: no such scope, another family's record, no chip, no trace.
    other = another_familys_scopes()
    assert read(record(hlo_scopes=other), {"op_seconds": {k: v for k, v in OP_SECONDS.items() if k in other}}) is None
    qwen = read_json(ROOT, "benchmark", "configs", "qwen3_next_80b_a3b.json")
    assert read(record(config=qwen), trace) is None  # a family whose file counts no such rule
    scalar = {k: v.replace("kda/rule", "gdn/rule") for k, v in SCOPES.items()}
    assert read(record(hlo_scopes=scalar), trace) is None  # the scalar rule's scope is another reader's
    assert read(record(config={}), trace) is None and read(record(hlo_scopes=None), trace) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), trace) is None
    assert read(record(), None) is None
    assert read({"spans": {}, "device": {"platform": "tpu"}}, trace) is None


def test_moe_route_share_holds_the_group_step_and_reads_nothing_without_one():
    read = harness.load_reader("model.moe_route_share")
    trace = {"op_seconds": OP_SECONDS}
    assert read(record(), trace) == pytest.approx(100 * IN_ROUTE / ONCE)
    other = another_familys_scopes()  # a router that picks inside no groups: the accepted expert cells
    assert any("moe/route" in v for v in other.values())
    assert read(record(hlo_scopes=other), {"op_seconds": {k: v for k, v in OP_SECONDS.items() if k in other}}) is None
    assert read(record(), None) is None and read(record(hlo_scopes=None), trace) is None


def test_the_accepted_readers_read_the_new_scopes():
    trace = {"op_seconds": OP_SECONDS}
    total = sum(OP_SECONDS.values())  # the accepted readers sum a loop's event with its body (ROADMAP B5)
    # The latent block's core: its two calls; the KDA block is no attention block.
    assert harness.load_reader("model.attention_share")(record(), trace) == pytest.approx(100 * 7.0 / total)
    assert harness.load_reader("kernel.attention_engaged_share")(record(), trace) == pytest.approx(100.0)
    # to_qkv and to_out of both blocks, the dense MLP, the shared expert and the grouped matmuls
    assert harness.load_reader("model.matmul_share")(record(), trace) == pytest.approx(100 * 24.0 / total)
    assert harness.load_reader("model.unowned_share")(record(), trace) == 0.0
    assert harness.load_reader("trainer.optimizer_share")(record(), trace) == pytest.approx(100 * 1.0 / total)
    assert harness.load_reader("model.recompute_share")(record(), trace) == pytest.approx(100 * 2.0 / total)
    assert harness.load_reader("model.mtp_share")(record(), trace) is None  # no module
    assert harness.load_reader("model.gdn_share")(record(), trace) is None  # no scalar-decay block
    assert harness.load_reader("kernel.gated_delta_roofline_share")(record(), trace) is None
    assert harness.load_reader("model.short_conv_share")(record(), trace) is None
    # The expert family's readers find this family's record as they find their own: its flops file answers
    # every function they look up, its configuration every key (a benchmark PR can list the cell by data alone).
    assert harness.load_reader("model.moe_routed_share")(record(), trace) == pytest.approx(100 * 8.0 / total)
    assert harness.load_reader("model.moe_dispatch_share")(record(), trace) == pytest.approx(100 * 6.0 / total)
    assert harness.load_reader("kernel.grouped_matmul_roofline_share")(record(), trace) is not None
    assert harness.load_reader("kernel.mla_attention_roofline_share")(record(), trace) is not None  # JoyAI's kernel shape
    assert harness.load_reader("kernel.gated_attention_roofline_share")(record(), trace) is None  # no such block


# ------------------------------------------------------------------- schema


def test_benchmark_json_holds_the_cell_and_its_files(bench):
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "train_tokens_fit"
    assert (cell["config"]["name"], cell["traffic"]) == ("ling_3.0_flash", "train_ep64_kda_resident_4k")
    config = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "ling_3.0_flash")
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert len(entry["source"]) <= 200 and entry["file"] == "benchmark/configs/ling_3.0_flash.json"
    assert entry["source"].startswith("https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json")
    assert (config["num_layers"], config["first_k_dense_replace"], config["num_experts"], config["vocab_size"]) == (
        6, 1, 8, 19648)
    assert (config["num_layers_published"], config["first_k_dense_replace_published"], config["num_experts_published"],
            config["vocab_size_published"]) == (42, 2, 512, 157184)
    assert config["num_hidden_layers"] == 42 and config["expert_offset"] == 0
    for key in ("what", "deployment", "arithmetic", "compiled_step", "floors", "what_it_skews", "mtp", "tower"):
        assert config["cut"][key]
    for key in ("head", "mtp", "kda_safe_gate", "kda_no_rotary", "kda_gates_full_rank", "kda_output_norm",
                "latent_use_qk_norm", "latent_gate", "rotary", "group_score", "swiglu_clamp", "bias_update_rate",
                "balance_term", "adam_b1_b2_eps", "peak_learning_rate", "weight_decay", "clip_grad_norm",
                "sequence_length", "documents", "precision", "initial_values", "initialisation"):
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


def test_every_key_of_the_catalog_row_is_in_the_file_or_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash-VL")
    config = published()
    differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
    # num_layers is a key of its own beside num_hidden_layers, which stays the published 42
    assert differs == set(REDUCED) - {"num_layers"}
    assert not [k for k in config["reduced"] if any(w in k for w in schema.WIDTH_WORDS)]
    assert config["source"].startswith(row["source_url"])
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
                  "num_attention_heads", "head_dim", "num_experts_per_tok", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "n_group", "topk_group", "short_conv_kernel_size",
                  "layer_group_size", "kda_lower_bound", "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert config[width] == row["config"][width]
