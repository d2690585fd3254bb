"""The Xing4.0 cell's files: the token driver end to end at a toy size on the
CPU with the hyper-connected expert family (sound run correct, the int8
control not), the FLOP and stream-byte counts against a hand count, the two
readers on a hand-made table, and the schema's verdict on ``BENCHMARK.json``
as this cell leaves it."""

import copy
import json
import math
import os
import time

import pytest

from benchmark import run as harness, schema
from benchmark.flops import joyai as joyai_flops, xing as flops

from conftest import FIXTURES, ROOT, read_json

TOY = os.path.join(FIXTURES, "toy_xing")
CELL = "xing.train_ep8_4k"
NEW_METRICS = {"model.hc_share": ("lower", "models"), "kernel.hc_stream_roofline_share": ("higher", "kernels")}
REDUCED = ["num_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]


def published():
    return read_json(ROOT, "benchmark", "configs", "xing4_29b_a4b.json")


@pytest.fixture()
def toy_cell():
    return {
        "name": "toy.xing_train", "chips": 1,
        "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"),
        "limits": read_json(TOY, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_xing", "source": "toy", "file": "benchmark/tests/fixtures/toy_xing/config.json",
        "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_xing", "traffic": "toy_tokens", "chips": 1, "why": "toy",
    })
    for metric in extended["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(toy_cell["name"])
    return extended


@pytest.mark.parametrize("arm", ["sound", "control"])
def test_token_driver_carries_the_hyper_connected_family_and_decides_correct(toy_bench, toy_cell, arm):
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
    config, mix = published(), read_json(ROOT, "benchmark", "traffic", "train_ep8_resident_4k.json")
    assert mix["driver"] == "train_tokens_fit" and (mix["pool_batches"], mix["followed_steps"]) == (4, 3)
    assert mix["train_config"]["aux_loss_weight"] == config["recipe"]["balance_alpha"] == 1e-4
    assert mix["train_config"]["log_every_steps"] == 1
    assert config["recipe"]["entropy_weight"] == 0.0  # read by the driver, not by this family
    assert config["model_overrides"] == {
        "experts_held": [config["expert_offset"], config["n_routed_experts"]],
        "first_dense": config["first_k_dense_replace"], "mtp_modules": config["num_nextn_predict_layers"],
    }
    assert config["train"]["remat"] is True and config["train"]["per_chip_batch"] in (1, 2)
    assert (config["vocab_size"], config["num_layers"], config["sequence_length"]) == (16384, 5, 4096)
    assert config["sequence_length"] == config["rope_scaling"]["original_max_position_embeddings"]
    from sav_tpu.models.registry import _REGISTRY

    cls, registered = _REGISTRY["xing4_0_29b_a4b"]
    assert cls.bias_update_rate == config["recipe"]["bias_update_rate"] == 1e-3
    source = {"embed_dim": "hidden_size", "num_layers": "num_hidden_layers", "num_heads": "num_attention_heads",
              "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank", "nope_ch": "qk_nope_head_dim",
              "rope_ch": "qk_rope_head_dim", "v_ch": "v_head_dim", "mlp_ch": "intermediate_size",
              "expert_ch": "moe_intermediate_size", "num_experts": "n_routed_experts_published",
              "top_k": "num_experts_per_tok", "routed_scale": "routed_scaling_factor",
              "first_dense": "first_k_dense_replace_published", "mtp_modules": "num_nextn_predict_layers_published",
              "rope_theta": "rope_theta", "rope_scaling": "rope_scaling", "norm_eps": "rms_norm_eps",
              "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters", "hc_eps": "hc_eps"}
    assert {k: registered[k] for k in source} == {k: config[v] for k, v in source.items()}
    assert registered["hc_res_clamp"] == (config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"])


def test_the_cuts_parameters_are_the_files_arithmetic():
    """The tree the cell trains, counted from shapes alone: 759,346,190
    parameters, 12.15 GB of state at 16 bytes each."""
    import jax
    import jax.numpy as jnp

    from sav_tpu.models import create_model

    config = published()
    model = create_model(config["model_name"], num_classes=config["vocab_size"], dtype=jnp.bfloat16,
                         num_layers=config["num_layers"], **config["model_overrides"])
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = jax.eval_shape(lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))["params"]
    count = {k: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(v)) for k, v in tree.items()}
    assert (count["layer_0"], count["layer_1"]) == (128_196_918, 128_426_294)
    assert count["embed"] + count["lm_head"] + count["final_norm"] == 117_444_096 and "mtp" not in count
    assert sum(count.values()) == 759_346_190
    for number in ("759,346,190", "128,426,294", "128,196,918", "117,444,096", "12.15 GB"):
        assert number in config["cut"]["arithmetic"]
    hc = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree["layer_1"]["hc_attn"]))
    assert 2 * hc == 688_182 == 2 * (14_336 * 24 + 3 + 24)


# ------------------------------------------------------------------- counts


def test_flops_against_a_hand_count():
    config = published()
    # Latent attention, multiply-adds a token: q_a 3584x768, q_b 768x(32x192), kv_a 3584x576,
    # kv_b 512x(32x256), o (32x128)x3584.
    projections = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    assert projections == 28_409_856  # the arithmetic's 28,411,136 parameters less the two inner norms' 1,280
    core = 32 * (192 + 128) * 4097 / 2  # causal: (S + 1) / 2 visible pairs a position
    attention = 2 * projections + 2 * core
    dense, expert = 2 * 3 * 3584 * 9216, 2 * 3 * 3584 * 1024
    router, head = 2 * 3584 * 64, 2 * 3584 * 16384
    routed = 0.5 * expert  # 4 a token x 8 of 64 held
    # a sublayer's path: the projection onto 24 maps, u, H_res X, h_post y
    path = 2 * (14336 * 24 + 14336 + 16 * 3584 + 14336)
    assert path == 860_160
    token = 5 * (attention + 2 * path) + dense + 4 * (router + expert + routed) + head
    assert flops.forward_flops_per_image(config) == pytest.approx(4096 * token, rel=1e-12)
    assert flops.train_flops_per_image(config) * 2 == pytest.approx(23.40e12, rel=1e-3)  # a step of 2 sequences
    owners = flops.forward_flops_by_owner(config)
    share = {k: round(100 * v / sum(owners.values()), 1) for k, v in owners.items()}
    assert share == {"mla_projections": 29.8, "attention_core": 22.0, "dense_mlp": 20.8, "router": 0.2,
                     "shared_experts": 9.3, "routed_experts": 4.6, "hyper_connections": 0.9, "head": 12.3,
                     "mtp": 0.0}
    assert flops.held_routings_per_token(config) == 0.5
    # With the module (the published model): its layer, its two paths, eh_proj and a second head.
    with_module = flops.forward_flops_by_owner({**config, "num_nextn_predict_layers": 1})
    assert with_module["mtp"] == pytest.approx(
        4096 * (2 * 7168 * 3584 + attention + router + expert + routed + 2 * path + head), rel=1e-12)


def test_the_kernels_floors_are_the_expert_familys_at_these_widths():
    config = published()
    for name in ("attention_floor_seconds", "grouped_matmul_floor_seconds", "held_routings_per_token"):
        assert getattr(flops, name) is getattr(joyai_flops, name)  # what the accepted readers look up
    floor = flops.attention_floor_seconds(config, 2, 197e12, 819e9)
    assert floor["forward"] == pytest.approx(64 * 640 * (4096 * 4097 / 2) / 197e12) and floor["forward_bound"] == "flops"
    one = 2 * 4096 * 3584 * 1024
    assert joyai_flops.grouped_matmul_flops(config, 4096) == {"forward": 3 * one, "backward": 6 * one}
    kernels, rows = 3 * 8 * 3584 * 1024 * 2, 4096 * (2 * (3584 + 1024) + 1024 + 3584) * 2
    assert flops.grouped_matmul_floor_seconds(config, 4096, 197e12, 819e9)["forward"] == pytest.approx(
        max(3 * one / 197e12, (kernels + rows) / 819e9))


def test_the_residual_paths_bytes_against_a_hand_count():
    config = published()
    # A token and sublayer, forward: 4 streams in, y in, 4 streams out, bfloat16.
    assert flops.hc_stream_bytes_per_token(config) == 9 * 3584 * 2 == 64_512
    forward = 10 * 8192 * 64_512  # five layers' two sublayers, 8,192 tokens
    assert forward == 5_284_823_040
    assert flops.hc_stream_floor_seconds(config, 8192, False, 819e9) == pytest.approx(3 * forward / 819e9)
    assert flops.hc_stream_floor_seconds(config, 8192, True, 819e9) == pytest.approx(4 * forward / 819e9)
    assert flops.hc_stream_floor_seconds(config, 8192, True, 819e9) == pytest.approx(25.81e-3, rel=1e-3)
    with_module = {**config, "num_nextn_predict_layers": 1}
    assert flops.hc_stream_floor_seconds(with_module, 8192, True, 819e9) == pytest.approx(4 * 1.2 * forward / 819e9)


# ------------------------------------------------------------------ readers

STEP = "jit(_train_step_impl)/"
FWD = STEP + "jvp(JoyAILM)/"
BWD = STEP + "transpose(jvp(JoyAILM))/jvp(JoyAILM)/checkpoint/"
SCOPES = {
    "pre.1": FWD + "layer_1/hc_attn/hc/pre/dot_general",
    "sinkhorn.2": FWD + "layer_1/hc_attn/hc/sinkhorn/div",
    "post.3": FWD + "layer_1/hc/post/add",
    "pre.4": BWD + "rematted_computation/layer_1/hc_ffn/hc/pre/mul",
    "post.5": BWD + "layer_1/hc/post/mul",
    "attn.6": FWD + "layer_1/LatentSelfAttentionBlock_0/pallas_call",
    "qkv.7": FWD + "layer_1/LatentSelfAttentionBlock_0/to_qkv/q_b/dot_general",
    "gmm.8": FWD + "layer_1/moe/experts/fc1/jit(gmm)/pallas_call",
    "head.9": FWD + "lm_head/checkpoint/dot_general",
    "fusion.10": STEP + "optimizer/add",
    "fused.11": FWD + "layer_0/hc/post/add;" + FWD + "layer_0/hc_ffn/hc/pre/reduce_sum",
}
OP_SECONDS = {"pre.1": 2.0, "sinkhorn.2": 1.0, "post.3": 3.0, "pre.4": 2.0, "post.5": 4.0, "attn.6": 5.0,
              "qkv.7": 6.0, "gmm.8": 2.0, "head.9": 4.0, "fusion.10": 1.0, "fused.11": 1.0}
TOTAL, IN_HC = sum(OP_SECONDS.values()), 13.0


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


def plain_residual_scopes():
    """A program whose residual is one array: the parent's, another family's."""
    return {k: v for k, v in SCOPES.items() if "/hc/" not in v}


def test_hc_share_is_the_three_scopes_forward_recomputed_and_backward():
    read = harness.load_reader("model.hc_share")
    trace = {"op_seconds": OP_SECONDS}
    assert read(record(), trace) == pytest.approx(100 * IN_HC / TOTAL)
    plain = {k: v for k, v in OP_SECONDS.items() if k in plain_residual_scopes()}
    assert read(record(hlo_scopes=plain_residual_scopes()), {"op_seconds": plain}) is None
    assert read(record(), None) is None and read(record(hlo_scopes=None), trace) is None
    # The module's name is not the scope: a layer's ``hc_attn`` holds parameters, ``hc`` the work.
    only_module = {"x.1": FWD + "layer_1/hc_attn/param_cast"}
    assert read(record(hlo_scopes=only_module), {"op_seconds": {"x.1": 1.0}}) is None


def test_hc_stream_roofline_share_counts_the_same_bytes_whatever_runs():
    read = harness.load_reader("kernel.hc_stream_roofline_share")
    config = published()
    trace = {"op_seconds": OP_SECONDS}
    least = 2 * flops.hc_stream_floor_seconds(config, 2 * 4096, True, 819e9)  # two traced steps, recomputed
    assert read(record(), trace) == pytest.approx(100 * least / IN_HC)
    kept = {k: v.replace("rematted_computation/", "") for k, v in SCOPES.items()}
    once = 2 * flops.hc_stream_floor_seconds(config, 2 * 4096, False, 819e9)
    assert read(record(hlo_scopes=kept), trace) == pytest.approx(100 * once / IN_HC)
    # Nothing to read: no such scope (the parent), another family's record, no chip, no trace.
    plain = {k: v for k, v in OP_SECONDS.items() if k in plain_residual_scopes()}
    assert read(record(hlo_scopes=plain_residual_scopes()), {"op_seconds": plain}) is None
    joyai = read_json(ROOT, "benchmark", "configs", "joyai_llm_flash.json")
    assert read(record(config=joyai), trace) is None  # a family whose file counts no such bytes
    assert read(record(config={}), trace) is None and read(record(hlo_scopes=None), trace) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), trace) is None
    assert read(record(), None) is None
    assert read({"spans": {}, "device": {"platform": "tpu"}}, trace) is None


def test_the_accepted_readers_read_the_new_scopes():
    trace = {"op_seconds": OP_SECONDS}
    assert harness.load_reader("model.attention_share")(record(), trace) == pytest.approx(100 * 5.0 / TOTAL)
    # to_qkv and the grouped matmuls; no operation of the residual path is a weight matmul
    assert harness.load_reader("model.matmul_share")(record(), trace) == pytest.approx(100 * 8.0 / TOTAL)
    assert harness.load_reader("model.unowned_share")(record(), trace) == 0.0
    assert harness.load_reader("trainer.optimizer_share")(record(), trace) == pytest.approx(100 * 1.0 / TOTAL)
    assert harness.load_reader("model.recompute_share")(record(), trace) == pytest.approx(100 * 2.0 / TOTAL)
    assert harness.load_reader("model.mtp_share")(record(), trace) is None  # the cut runs no module
    # The expert family's five readers find this family's record as they find their own.
    assert harness.load_reader("model.moe_routed_share")(record(), trace) == pytest.approx(100 * 2.0 / TOTAL)
    assert harness.load_reader("kernel.mla_attention_roofline_share")(record(), trace) is not None
    assert harness.load_reader("kernel.grouped_matmul_roofline_share")(record(), trace) is not None


# ------------------------------------------------------------------- schema


def test_benchmark_json_holds_the_cell_and_its_files(bench):
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "train_tokens_fit"
    config = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "xing4_29b_a4b")
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert len(entry["source"]) <= 200 and entry["file"] == "benchmark/configs/xing4_29b_a4b.json"
    assert entry["source"].startswith("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
    assert (config["num_layers"], config["first_k_dense_replace"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (5, 1, 8, 16384, 0)
    assert (config["num_layers_published"], config["first_k_dense_replace_published"],
            config["n_routed_experts_published"], config["vocab_size_published"],
            config["num_nextn_predict_layers_published"]) == (40, 2, 64, 131072, 1)
    for key in ("what", "deployment", "arithmetic", "floors", "what_it_skews"):
        assert config["cut"][key]
    for key in ("hc_eps_place", "sinkhorn_order", "clamp_place", "initial_values", "ends", "mtp_path", "yarn"):
        assert config["assumed"][key]
    assert set(cell["limits"]) >= {"first_grad_rel_diff", "update_rel_diff", "compiles_in_window"}
    for name, (better, layer) in NEW_METRICS.items():
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_img_s_chip"
        assert metric["source"] == "device_trace" and metric["unit"] == "%"
        assert (metric["better"], metric["layer"]) == (better, layer)
        assert callable(harness.load_reader(name))
    assert [m["name"] for m in bench["per_layer"]][-2:] == list(NEW_METRICS)
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1  # the quarter rule: one of six
    assert len(bench["configs"]) == 5 and len(bench["workloads"]) == 6


def test_every_key_of_the_catalog_row_is_in_the_file_or_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
    config = published()
    differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
    # num_layers is a key of its own beside num_hidden_layers, which stays the published 40
    assert differs == set(REDUCED) - {"num_layers"}
    assert config["rope_scaling"] == row["config"]["rope_scaling"]  # a nested group, copied whole
    assert not [k for k in config["reduced"] if any(w in k for w in schema.WIDTH_WORDS)]
