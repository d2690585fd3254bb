"""The JoyAI-LLM-Flash cell's files: the token driver end to end at a toy
size on the CPU with the expert family (sound run correct, the int8 control
not), the FLOP and roofline counts against a hand count, the five readers on
hand-made tables, and the schema's verdict on ``BENCHMARK.json`` as this cell
leaves it."""

import copy
import os
import time

import pytest

from benchmark import run as harness, schema
from benchmark.flops import joyai as flops

from conftest import FIXTURES, ROOT, read_json

TOY = os.path.join(FIXTURES, "toy_joyai")
CELL = "joyai.train_ep16_4k"
NEW_METRICS = (
    "model.moe_routed_share", "model.moe_dispatch_share", "model.mtp_share",
    "kernel.mla_attention_roofline_share", "kernel.grouped_matmul_roofline_share",
)


def published():
    return read_json(ROOT, "benchmark", "configs", "joyai_llm_flash.json")


@pytest.fixture()
def toy_cell():
    return {
        "name": "toy.joyai_train", "chips": 1,
        "config": read_json(TOY, "config.json"),
        "mix": read_json(TOY, "mix.json"),
        "limits": read_json(TOY, "limits.json")["limits"],
    }


@pytest.fixture()
def toy_bench(bench, toy_cell):
    extended = copy.deepcopy(bench)
    extended["configs"].append({
        "name": "toy_joyai", "source": toy_cell["config"]["source"],
        "file": "benchmark/tests/fixtures/toy_joyai/config.json", "reduced": [], "why": "toy",
    })
    extended["workloads"].append({
        "name": toy_cell["name"], "config": "toy_joyai", "traffic": "toy_tokens", "chips": 1, "why": "toy",
    })
    for metric in extended["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(toy_cell["name"])
    return extended


@pytest.mark.parametrize("arm", ["sound", "control"])
def test_token_driver_carries_the_expert_family_and_decides_correct(toy_bench, toy_cell, arm):
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


def test_the_mix_and_the_recipe_agree_and_the_driver_finds_its_keys():
    config, mix = published(), read_json(ROOT, "benchmark", "traffic", "train_ep16_resident_4k.json")
    assert mix["driver"] == "train_tokens_fit"
    assert mix["train_config"]["aux_loss_weight"] == config["recipe"]["balance_alpha"] == 1e-4
    assert config["recipe"]["entropy_weight"] == 0.0  # read by the driver, not by this family
    assert config["model_overrides"] == {"experts_held": [config["expert_offset"], config["n_routed_experts"]]}
    assert config["train"] == {**config["train"], "per_chip_batch": 2, "remat": True}
    assert (config["vocab_size"], config["num_layers"], config["sequence_length"]) == (16160, 5, 4096)
    from sav_tpu.train.tasks import MTPTokenPrediction

    assert MTPTokenPrediction.mtp_weight == config["recipe"]["mtp_lambda"] == 0.3
    from sav_tpu.models.registry import _REGISTRY

    cls, registered = _REGISTRY["joyai_llm_flash"]
    assert cls.bias_update_rate == config["recipe"]["bias_update_rate"] == 1e-3
    source = {"embed_dim": "hidden_size", "num_layers": "num_hidden_layers", "num_heads": "num_attention_heads",
              "q_rank": "q_lora_rank", "kv_rank": "kv_lora_rank", "nope_ch": "qk_nope_head_dim",
              "rope_ch": "qk_rope_head_dim", "v_ch": "v_head_dim", "mlp_ch": "intermediate_size",
              "expert_ch": "moe_intermediate_size", "num_experts": "n_routed_experts_published",
              "top_k": "num_experts_per_tok", "routed_scale": "routed_scaling_factor",
              "first_dense": "first_k_dense_replace", "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps"}
    assert {k: registered[k] for k in source} == {k: config[v] for k, v in source.items()}


# ------------------------------------------------------------------- counts


def test_flops_against_a_hand_count():
    config = published()
    # Latent attention, multiply-adds a token: q_a 2048x1536, q_b 1536x(32x192), kv_a 2048x576,
    # kv_b 512x(32x256), o (32x128)x2048.
    projections = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert projections == 26_345_472  # ISSUE 30's 26,347,520 parameters less the two inner norms' 2,048
    core = 32 * (192 + 128) * 4097 / 2  # causal: (S + 1) / 2 visible pairs a position
    attention = 2 * projections + 2 * core
    dense, expert = 2 * 3 * 2048 * 7168, 2 * 3 * 2048 * 768
    router, head, eh = 2 * 2048 * 256, 2 * 2048 * 16160, 2 * 4096 * 2048
    routed = 0.5 * expert  # 8 a token x 16 of 256 held
    token = 5 * attention + dense + 4 * (router + expert + routed) + head \
        + (eh + attention + router + expert + routed + head)
    assert flops.forward_flops_per_image(config) == pytest.approx(4096 * token, rel=1e-12)
    assert 3 * token == pytest.approx(2.643e9, rel=1e-3)  # ISSUE 30's 2.64 GFLOP a token trained
    assert flops.train_flops_per_image(config) * 2 == pytest.approx(21.65e12, rel=1e-3)  # a step of 2 sequences
    owners = flops.forward_flops_by_owner(config)
    share = {k: round(100 * v / sum(owners.values()), 1) for k, v in owners.items()}
    assert share == {"mla_projections": 29.9, "attention_core": 23.8, "dense_mlp": 10.0, "router": 0.5,
                     "shared_experts": 4.3, "routed_experts": 2.1, "head": 7.5, "mtp": 21.9}
    assert flops.held_routings_per_token(config) == 0.5


def test_kernel_floors_against_a_hand_count():
    config = published()
    pairs = 4096 * 4097 / 2
    assert flops.attention_forward_flops(4096, 192, 128) == 2 * (192 + 128) * pairs
    assert flops.attention_backward_flops(4096, 192, 128) == 2 * (3 * 192 + 2 * 128) * pairs
    # At equal heads the two counts are the Ouro file's: 4 D a pair, and 2.5 times that.
    assert flops.attention_backward_flops(4096, 128, 128) == 2.5 * flops.attention_forward_flops(4096, 128, 128)
    assert flops.attention_forward_bytes(4096, 192, 128) == 4096 * (192 + 192 + 128 + 128) * 2 + 4 * 4096
    assert flops.attention_backward_bytes(4096, 192, 128) == 4096 * (4 * 192 + 4 * 128) * 2 + 4 * 4096
    floor = flops.attention_floor_seconds(config, 2, 197e12, 819e9)
    assert floor["forward"] == pytest.approx(64 * 640 * pairs / 197e12) and floor["forward_bound"] == "flops"
    assert floor["backward"] == pytest.approx(2.6 * floor["forward"]) and floor["backward_bound"] == "flops"
    assert flops.attention_floor_seconds(config, 2, 197e12, 1e9)["forward_bound"] == "bytes"
    # The grouped matmuls at the expected 4,096 routings on the 16 experts held.
    one = 2 * 4096 * 2048 * 768
    assert flops.grouped_matmul_flops(config, 4096) == {"forward": 3 * one, "backward": 6 * one}
    kernels, rows = 3 * 16 * 2048 * 768 * 2, 4096 * (2 * (2048 + 768) + 768 + 2048) * 2
    assert flops.grouped_matmul_bytes(config, 4096) == {"forward": kernels + rows, "backward": 2 * (kernels + rows)}
    floor = flops.grouped_matmul_floor_seconds(config, 4096, 197e12, 819e9)
    # 151 MB of kernels for 39 GFLOP: the bandwidth bounds both directions at this micro-batch.
    assert floor["forward_bound"] == floor["backward_bound"] == "bytes"
    assert floor["forward"] == pytest.approx((kernels + rows) / 819e9)
    assert flops.grouped_matmul_floor_seconds(config, 16 * 4096, 197e12, 819e9)["forward"] < 16 * floor["forward"]


# ------------------------------------------------------------------ readers

STEP = "jit(_train_step_impl)/"
FWD, BWD = STEP + "jvp(JoyAILM)/", STEP + "transpose(jvp(JoyAILM))/jvp(JoyAILM)/checkpoint/"
ATTN = "LatentSelfAttentionBlock_0/pallas_call"
SCOPES = {
    "attn_fwd.1": FWD + "layer_1/" + ATTN,
    "attn_dq.2": BWD + "layer_1/" + ATTN,
    "attn_dkv.3": BWD + "layer_1/" + ATTN,
    "qkv.4": FWD + "layer_1/LatentSelfAttentionBlock_0/to_qkv/q_b/dot_general",
    "route.5": FWD + "layer_1/moe/route/top_k",
    "sort.6": FWD + "layer_1/moe/dispatch/sort",
    "gmm.7": FWD + "layer_1/moe/experts/fc1/jit(gmm)/pallas_call",
    "gmm.8": BWD + "rematted_computation/layer_1/moe/experts/fc1/jit(gmm)/pallas_call",
    "tgmm.9": BWD + "layer_1/moe/experts/fc2/jit(tgmm)/pallas_call",
    "combine.10": BWD + "layer_1/moe/combine/gather",
    "shared.11": FWD + "layer_1/moe/shared/fc1/gate/dot_general",
    "mtp_gmm.12": STEP + "jvp(JoyAILM)/mtp/layer/moe/experts/fc2/jit(gmm)/pallas_call",
    "mtp_head.13": STEP + "jvp(JoyAILM)/mtp/lm_head/checkpoint/dot_general",
    "head.14": FWD + "lm_head/checkpoint/dot_general",
    "fusion.15": STEP + "optimizer/add",
    "dense.16": FWD + "layer_0/GatedFFBlock_0/fc1/up/dot_general",
}
OP_SECONDS = {"attn_fwd.1": 2.0, "attn_dq.2": 3.0, "attn_dkv.3": 5.0, "qkv.4": 6.0, "route.5": 1.0, "sort.6": 2.0,
              "gmm.7": 1.0, "gmm.8": 1.0, "tgmm.9": 2.0, "combine.10": 3.0, "shared.11": 2.0, "mtp_gmm.12": 1.0,
              "mtp_head.13": 4.0, "head.14": 4.0, "fusion.15": 1.0, "dense.16": 2.0}
TOTAL = sum(OP_SECONDS.values())


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


def dense_model_scopes():
    """A program without an expert layer, an MTP module or a latent block: the parent's."""
    return {
        k: v.replace("moe/", "GatedFFBlock_0/").replace("mtp/", "").replace("Latent", "")
        for k, v in SCOPES.items()
    }


def test_moe_shares_split_the_routed_half_from_the_matmuls_it_feeds():
    routed, dispatch = harness.load_reader("model.moe_routed_share"), harness.load_reader("model.moe_dispatch_share")
    trace = {"op_seconds": OP_SECONDS}
    # route 1 + sort 2 + experts 1 + 1 + 2 + 1 (the MTP layer's) + combine 3; the shared expert is not routed
    assert routed(record(), trace) == pytest.approx(100 * 11.0 / TOTAL)
    assert dispatch(record(), trace) == pytest.approx(100 * 6.0 / TOTAL)
    for read in (routed, dispatch):
        assert read(record(hlo_scopes=dense_model_scopes()), trace) is None
        assert read(record(), None) is None and read(record(hlo_scopes=None), trace) is None


def test_mtp_share_is_the_module_with_its_head():
    read = harness.load_reader("model.mtp_share")
    assert read(record(), {"op_seconds": OP_SECONDS}) == pytest.approx(100 * 5.0 / TOTAL)
    assert read(record(hlo_scopes=dense_model_scopes()), {"op_seconds": OP_SECONDS}) is None
    assert read(record(), None) is None


def test_mla_roofline_share_counts_the_latent_blocks_calls_only():
    read = harness.load_reader("kernel.mla_attention_roofline_share")
    floor = flops.attention_floor_seconds(published(), 2, 197e12, 819e9)
    least = 2 * (1 * floor["forward"] + floor["backward"])  # one forward, one backward of two kernels, two steps
    assert read(record(), {"op_seconds": OP_SECONDS}) == pytest.approx(100 * least / 10.0)
    # The grouped matmuls are Mosaic calls too and are not this metric's.
    only_gmm = {k: v for k, v in SCOPES.items() if "gmm" in k}
    assert read(record(kernel_calls=only_gmm), {"op_seconds": OP_SECONDS}) is None
    assert read(record(kernel_calls=None), {"op_seconds": OP_SECONDS}) is None  # the parent, another driver
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), {"op_seconds": OP_SECONDS}) is None
    ouro = read_json(ROOT, "benchmark", "configs", "ouro_2.6b.json")
    assert read(record(config=ouro), {"op_seconds": OP_SECONDS}) is None  # a family that counts no such kernel
    assert read({"spans": {}, "device": {"platform": "tpu"}}, {"op_seconds": OP_SECONDS}) is None


def test_grouped_matmul_roofline_share_counts_layers_and_recomputation():
    read = harness.load_reader("kernel.grouped_matmul_roofline_share")
    config = published()
    floor = flops.grouped_matmul_floor_seconds(config, 2 * 4096 * 0.5, 197e12, 819e9)
    # 4 routed layers and the MTP module's; the forward counted twice where the trace holds recomputed calls
    least = 2 * 5 * (2 * floor["forward"] + floor["backward"])
    assert read(record(), {"op_seconds": OP_SECONDS}) == pytest.approx(100 * least / 5.0)
    kept = {k: v.replace("rematted_computation/", "") for k, v in SCOPES.items()}
    once = 2 * 5 * (floor["forward"] + floor["backward"])
    assert read(record(hlo_scopes=kept), {"op_seconds": OP_SECONDS}) == pytest.approx(100 * once / 5.0)
    assert read(record(hlo_scopes=dense_model_scopes()), {"op_seconds": OP_SECONDS}) is None
    assert read(record(hlo_scopes=None), {"op_seconds": OP_SECONDS}) is None
    assert read(record(device={"platform": "cpu", "kind": "cpu"}), {"op_seconds": OP_SECONDS}) is None


def test_the_accepted_readers_read_the_new_scopes():
    trace = {"op_seconds": OP_SECONDS}
    assert harness.load_reader("model.attention_share")(record(), trace) == pytest.approx(100 * 10.0 / TOTAL)
    # to_qkv, and every SwiGLU's matmuls, the routed experts' grouped ones too
    assert harness.load_reader("model.matmul_share")(record(), trace) == pytest.approx(100 * 15.0 / TOTAL)
    assert harness.load_reader("kernel.attention_engaged_share")(record(), trace) == pytest.approx(100.0)
    assert harness.load_reader("model.unowned_share")(record(), trace) == 0.0
    assert harness.load_reader("trainer.optimizer_share")(record(), trace) == pytest.approx(100 * 1.0 / TOTAL)
    assert harness.load_reader("model.recompute_share")(record(), trace) == pytest.approx(100 * 1.0 / TOTAL)


# ------------------------------------------------------------------- schema


def test_benchmark_json_holds_the_cell_and_its_files(bench):
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["mix"]["driver"] == "train_tokens_fit"
    config = cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == "joyai_llm_flash")
    assert entry["reduced"] == config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert len(entry["source"]) <= 200 and entry["file"] == "benchmark/configs/joyai_llm_flash.json"
    source = {"hidden_size": 2048, "num_attention_heads": 32, "q_lora_rank": 1536, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "intermediate_size": 7168,
              "moe_intermediate_size": 768, "num_experts_per_tok": 8, "n_shared_experts": 1,
              "routed_scaling_factor": 2.5, "rope_theta": 32000000, "num_hidden_layers": 40,
              "first_k_dense_replace": 1, "num_nextn_predict_layers": 1, "scoring_func": "sigmoid",
              "topk_method": "noaux_tc", "rope_interleave": True, "max_position_embeddings": 131072}
    assert {k: config[k] for k in source} == source
    assert (config["num_layers"], config["n_routed_experts"], config["vocab_size"]) == (5, 16, 16160)
    assert (config["num_layers_published"], config["n_routed_experts_published"],
            config["vocab_size_published"]) == (40, 256, 129280)
    assert set(cell["limits"]) >= {"first_grad_rel_diff", "update_rel_diff", "compiles_in_window"}
    for name in NEW_METRICS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_img_s_chip"
        assert metric["source"] == "device_trace" and metric["unit"] == "%"
        assert callable(harness.load_reader(name))
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1  # the quarter rule: one of five


def test_every_key_of_the_catalog_row_is_in_the_file_or_reduced():
    import json

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
    config = published()
    differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
    assert differs == {"n_routed_experts", "vocab_size"}  # num_layers is a key of its own beside num_hidden_layers
    assert not [k for k in config["reduced"] if any(w in k for w in schema.WIDTH_WORDS)]
