"""The Laguna family (three sliding-window layers to one full layer, WITH
DIFFERENT HEAD COUNTS, per-head output gates, two rotaries, softmax-routed
experts scaled by 2.5 with a gated shared expert, one leading dense layer)
against its plain float32 reference, at toy sizes on the CPU: hidden 64, five
layers in the cut's order (full + dense SwiGLU, then window x 3 and full on
expert layers), 9 query heads of 16 on 1 key/value head in a window layer and
6 in a full one, a window of 8 positions, 16 experts of which 4 a token,
vocabulary 97, 32 positions.

Tolerances. Program and reference both compute in float32 here, in different
orders (a sort and grouped matmuls against a loop over experts, the library's
rotary tables against the ramp written out), so ``TIGHT`` = 2e-5 of the
compared tensor's largest entry, as ``test_joyai.py``; the gradients of a
five-layer model are held to 1e-4. Each ASSUMED item of the configuration's
file, switched in the reference, moves the logits by far more than that."""

import copy
import gc
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights  # noqa: E402
from benchmark.reference import laguna as reference  # noqa: E402
from sav_tpu.models import create_model, model_supports, model_task  # noqa: E402
from sav_tpu.models.joyai import (  # noqa: E402
    KEPT_UNDER_REMAT,
    KEPT_UNDER_REMAT_BESIDE_WINDOWS,
    MIXER_BLOCKS,
    STAT_REDUCTIONS,
    LatentDecoderBlock,
)
from sav_tpu.models.layers.gated_attention import GatedSelfAttentionBlock, gate_granularity  # noqa: E402
from sav_tpu.models.registry import _REGISTRY  # noqa: E402
from sav_tpu.ops import attention as attention_ops  # noqa: E402
from sav_tpu.ops import rotary  # noqa: E402

TIGHT = 2e-5
VOCAB, SEQ, BATCH, EXPERTS, TOP_K, ALPHA, WINDOW = 97, 32, 2, 16, 4, 1e-3, 8
KINDS = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention")
YARN = {
    "rope_type": "yarn", "factor": 128.0, "original_max_position_embeddings": 16,
    "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 1.4852030263919618,
}
FULL = {"num_heads": 6, "kv_heads": 1, "head_ch": 16, "rotary_ch": 8, "gate": "head", "rope_theta": 5e5,
        "rope_scaling": YARN}
SLIDING = {"num_heads": 9, "kv_heads": 1, "head_ch": 16, "rotary_ch": 16, "gate": "head", "rope_theta": 1e4,
           "window": WINDOW}
SIZES = dict(embed_dim=64, num_layers=5, mixers=KINDS, mlp_ch=96, expert_ch=32, num_experts=EXPERTS, top_k=TOP_K,
             loss_block_tokens=16, gated_attention=FULL, sliding_attention=SLIDING)


def model_file(held=(0, EXPERTS)):
    """What ``benchmark/configs/laguna_s_2.1.json`` holds, at the toy sizes."""
    return {
        "hidden_size": 64, "num_layers": 5, "num_key_value_heads": 1, "head_dim": 16, "rms_norm_eps": 1e-6,
        "layer_types": list(KINDS), "num_attention_heads_per_layer": [6, 9, 9, 9, 6],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"], "sliding_window": WINDOW,
        "rope_parameters": {
            "full_attention": {"rope_theta": 500000, "partial_rotary_factor": 0.5, **YARN},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        },
        "intermediate_size": 96, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "moe_routed_scaling_factor": 2.5, "num_experts_published": EXPERTS, "expert_offset": held[0],
        "num_experts": held[1], "num_experts_per_tok": TOP_K, "vocab_size": VOCAB,
        "recipe": {"balance_alpha": ALPHA},
    }


def build(dtype=jnp.float32, **overrides):
    return create_model("laguna_s_2.1", num_classes=VOCAB, dtype=dtype, **{**SIZES, **overrides})


def draw(model, tokens, seed=11):
    abstract = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens[:, :-1], is_training=False)
    )["params"]
    return weights.draw_params(abstract, seed)


@pytest.fixture(scope="module", autouse=True)
def _leave_no_live_buffers():
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (BATCH, SEQ + 1), 0, VOCAB, jnp.int32)


@pytest.fixture(scope="module")
def params(tokens):
    return draw(build(), tokens)


def close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) <= tol * float(np.max(np.abs(want)))


def bias_rows():
    return {"select_bias": jnp.zeros((4, EXPERTS))}  # the family has no selection bias: the rows stay zero


def program_logits(params, tokens, **overrides):
    model = build(**overrides)
    return jax.jit(lambda p, t: model.apply({"params": p, "batch_stats": bias_rows()}, t, is_training=False))(
        params, tokens[:, :-1])["logits"]


def reference_logits(params, tokens, model=None):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([reference.sequence_logits(params, row[:-1], model or model_file()) for row in tokens])


# ------------------------------------------------------------- the registry


def test_the_registry_builds_the_published_forty_eight_layers():
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna_s_2.1.json")) as f:
        source = json.load(f)
    cls, registered = _REGISTRY["laguna_s_2.1"]
    assert tuple(registered["mixers"]) == tuple(source["layer_types"]) and len(registered["mixers"]) == 48
    assert registered["mixers"].count("sliding_attention") == 36 and registered["mixers"].count("full_attention") == 12
    full, sliding = registered["gated_attention"], registered["sliding_attention"]
    heads = {"full_attention": full["num_heads"], "sliding_attention": sliding["num_heads"]}
    assert [heads[kind] for kind in source["layer_types"]] == source["num_attention_heads_per_layer"]
    assert (full["kv_heads"], full["head_ch"]) == (sliding["kv_heads"], sliding["head_ch"]) == (8, 128)
    rope = source["rope_parameters"]
    assert sliding["window"] == source["sliding_window"] == 512 and "window" not in full
    assert sliding["rotary_ch"] == 128 * rope["sliding_attention"]["partial_rotary_factor"]
    assert full["rotary_ch"] == 128 * rope["full_attention"]["partial_rotary_factor"] == 64
    assert (sliding["rope_theta"], full["rope_theta"]) == (1e4, 5e5)
    assert {k: full["rope_scaling"][k] for k in YARN if k != "original_max_position_embeddings"} == {
        k: rope["full_attention"][k] for k in YARN if k != "original_max_position_embeddings"}
    assert full["rope_scaling"]["original_max_position_embeddings"] == 8192
    assert full["gate"] == sliding["gate"] == "head" and set(source["gating_types"]) == {"per_head"}
    assert (registered["num_layers"], registered["first_dense"]) == (48, len(source["mlp_only_layers"])) == (48, 1)
    assert (registered["num_experts"], registered["top_k"], registered["routed_scale"]) == (256, 10, 2.5)
    assert registered["scoring"] == "softmax" and registered["shared_gate"] and registered["mtp_modules"] == 0
    assert registered["bias_update_rate"] == 0.0 and not registered["norm_offset"]
    assert registered["kept_under_remat"] == KEPT_UNDER_REMAT_BESIDE_WINDOWS
    assert set(KEPT_UNDER_REMAT) <= set(KEPT_UNDER_REMAT_BESIDE_WINDOWS)
    assert model_task("laguna_s_2.1") == "tokens_mtp"
    for field in ("remat", "backend", "quant", "experts_held"):  # as the other expert families answer
        assert model_supports("laguna_s_2.1", field) == model_supports("qwen3_next_80b_a3b", field) is True
    tree = jax.eval_shape(lambda: create_model("laguna_s_2.1", num_classes=64).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32), is_training=False))["params"]
    shapes = [tree[f"layer_{i}"]["GatedSelfAttentionBlock_0"]["to_qkv"]["q"]["kernel"].shape for i in range(48)]
    assert shapes == [(3072, h * 128) for h in source["num_attention_heads_per_layer"]]
    outs = [tree[f"layer_{i}"]["GatedSelfAttentionBlock_0"]["to_out"]["kernel"].shape for i in range(48)]
    assert outs == [(h, 128, 3072) for h in source["num_attention_heads_per_layer"]]
    assert [i for i in range(48) if "GatedFFBlock_0" in tree[f"layer_{i}"]] == source["mlp_only_layers"]
    assert "lm_head" in tree and all("shared_gate" in tree[f"layer_{i}"]["moe"] for i in range(1, 48))
    assert MIXER_BLOCKS["sliding_attention"][0] is MIXER_BLOCKS["full_attention"][0] is GatedSelfAttentionBlock
    assert STAT_REDUCTIONS["attn_gate_mean_window"] is STAT_REDUCTIONS["attn_gate_mean_full"]


def test_the_tree_is_the_one_the_reference_reads(params):
    reference.check_layout(params, model_file())
    wrong = copy.deepcopy(model_file())
    wrong["num_attention_heads_per_layer"] = [6, 6, 9, 9, 6]
    with pytest.raises(ValueError, match="is not the configuration's"):
        reference.check_layout(params, wrong)
    block = params["layer_1"]["GatedSelfAttentionBlock_0"]
    assert sorted(block["to_qkv"]) == ["gate", "k", "k_norm", "q", "q_norm", "v"]
    assert block["to_qkv"]["gate"]["kernel"].shape == (64, 9) and block["to_out"]["kernel"].shape == (9, 16, 64)
    assert params["layer_0"]["GatedSelfAttentionBlock_0"]["to_qkv"]["gate"]["kernel"].shape == (64, 6)


# ------------------------------------------------------------- the block's forms


def test_the_gate_is_a_granularity():
    assert [gate_granularity(g) for g in (None, False, True, "lane", "head")] == [None, None, "lane", "lane", "head"]
    with pytest.raises(ValueError, match="attention gate"):
        gate_granularity("token")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    sizes = dict(num_heads=4, kv_heads=2, head_ch=16, rotary_ch=16, norm_offset=False)
    shapes = {}
    for gate in (None, "lane", "head"):
        block = GatedSelfAttentionBlock(gate=gate, **sizes)
        variables = block.init({"params": jax.random.PRNGKey(1)}, x)
        (_, stats), tree = block.apply(variables, x), variables["params"]["to_qkv"]
        shapes[gate] = (tree["q"]["kernel"].shape, "gate" in tree, sorted(stats))
    assert shapes[None] == ((64, 64), False, [])
    assert shapes["lane"] == ((64, 128), False, ["gate_mean"])  # the other half of W_q, as before
    assert shapes["head"] == ((64, 64), True, ["gate_mean", "gate_mean_full"])
    windowed = GatedSelfAttentionBlock(gate="head", window=4, **sizes)
    assert sorted(windowed.apply(windowed.init({"params": jax.random.PRNGKey(1)}, x), x)[1]) == [
        "gate_mean", "gate_mean_window"]


def test_yarn_tables_carry_the_attention_factor_and_the_library_agrees_with_the_ramp_by_hand():
    rope = model_file()["rope_parameters"]["full_attention"]
    by_hand = np.asarray(reference.yarn_frequencies(8, rope))
    library = np.asarray(rotary.yarn_inv_freq(8, 5e5, YARN))
    np.testing.assert_allclose(library, by_hand, rtol=1e-6)
    plain = 5e5 ** (-np.arange(4) / 4)
    assert np.any(by_hand < 0.999 * plain) and by_hand[0] == pytest.approx(plain[0])  # slow pairs slowed, the fastest kept
    sin, cos = rotary.half_split_tables(32, 8, 5e5, YARN)
    assert float(cos[0, 0]) == pytest.approx(YARN["attention_factor"])  # position 0: cos 1 times the factor
    assert float(jnp.max(jnp.abs(rotary.half_split_tables(32, 8, 5e5)[1]))) == pytest.approx(1.0)
    default = dict(YARN, attention_factor=None)
    assert float(rotary.half_split_tables(4, 8, 5e5, default)[1][0, 0]) == pytest.approx(0.1 * np.log(128.0) + 1.0)


def test_the_cores_run_under_scopes_that_tell_the_two_kinds_apart(tokens, params):
    model = build()
    text = jax.jit(lambda p, t: model.apply({"params": p, "batch_stats": bias_rows()}, t, is_training=False)).lower(
        params, tokens[:, :-1]).as_text(debug_info=True)
    for layer, kind in enumerate(KINDS):
        scope = "attn/full" if kind == "full_attention" else "attn/window"
        assert f"layer_{layer}/GatedSelfAttentionBlock_0/{scope}" in text
    assert "layer_0/GatedSelfAttentionBlock_0/attn/window" not in text


def test_the_dispatch_log_records_the_window_and_the_blocks_visited():
    attention_ops.clear_dispatch_log()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 768, 64))
    sizes = dict(num_heads=2, kv_heads=1, head_ch=128, rotary_ch=128, gate="head", norm_offset=False, backend="pallas")
    for window in (128, None):
        block = GatedSelfAttentionBlock(window=window, **sizes)
        jax.eval_shape(lambda: block.init({"params": jax.random.PRNGKey(1)}, x))
    banded, causal = attention_ops.snapshot_dispatch_log()
    assert banded["window"] == 128 and "window" not in causal and banded["shape"] == causal["shape"]
    assert (banded["kv_blocks_visited"], banded["kv_blocks_causal"]) == (5, 6)  # 256-row blocks: cell (2, 0) is skipped


# ----------------------------------------------- program against the reference


def test_logits_match_the_reference(tokens, params):
    got, want = program_logits(params, tokens), reference_logits(params, tokens)
    assert got.shape == (BATCH, SEQ, VOCAB) and close(got, want)
    # The flash kernels' path in the interpreter (two layouts of the banded kernel: heads of 16 run head-major).
    assert close(program_logits(params, tokens, backend="pallas"), want, 5e-5)


def _switched(name):
    """The reference with one ASSUMED item of the configuration's file (or one
    published mechanism) switched: ``(patched module attributes, model file)``."""
    model, patch = model_file(), {}
    if name == "qk_norm":  # no per-head norms
        real = reference.norm
        patch["norm"] = lambda x, p, eps: x if p["scale"].shape == (16,) else real(x, p, eps)
    elif name == "hidden_act":  # GELU in the SwiGLUs
        patch["swiglu"] = lambda x, gate, up, down: (jax.nn.gelu(x @ gate) * (x @ up)) @ down
    elif name == "router_activation":  # sigmoid scores
        def route(x, p, model):
            scores = jax.nn.sigmoid(x @ p["route"]["kernel"])
            _, chosen = jax.lax.top_k(scores, model["num_experts_per_tok"])
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            return scores, chosen, model["moe_routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)
        patch["route"] = route
    elif name == "shared_expert_gate":  # an ungated shared expert
        patch["shared_part"] = lambda x, p: reference.mlp(x, p["shared"])
    elif name == "window":  # the band left out: the causal mask alone in the window layers
        model["sliding_window"] = SEQ
    elif name == "routed_scale":
        model["moe_routed_scaling_factor"] = 1.0
    elif name == "attention_factor":  # the factor not on the tables
        model["rope_parameters"]["full_attention"]["attention_factor"] = 1.0
    elif name == "yarn":  # plain frequencies in the full layers
        model["rope_parameters"]["full_attention"]["rope_type"] = "default"
    elif name == "partial_rotary":  # the whole head turned in the full layers
        model["rope_parameters"]["full_attention"]["partial_rotary_factor"] = 1
    elif name == "window_theta":
        model["rope_parameters"]["sliding_attention"]["rope_theta"] = 500000
    elif name == "head_gate":  # no output gate
        real = reference.attention
        patch["attention"] = lambda x, p, kind, heads, model: real(
            x, {**p, "to_qkv": {**p["to_qkv"], "gate": {"kernel": jnp.full_like(p["to_qkv"]["gate"]["kernel"], 1e4)}}},
            kind, heads, model)
    return patch, model


@pytest.fixture
def fresh_traces(monkeypatch):
    """The reference's layers are traced under ``jax.checkpoint`` and jit, whose caches do not see a patched
    module attribute: cleared before a switched reference is traced and after, so that no other test meets it."""
    jax.clear_caches()
    yield monkeypatch
    jax.clear_caches()


@pytest.mark.parametrize("name", [
    "qk_norm", "hidden_act", "router_activation", "shared_expert_gate",  # the configuration file's ASSUMED items
    "window", "routed_scale", "attention_factor", "yarn", "partial_rotary", "window_theta", "head_gate",
])
def test_each_assumed_item_and_each_mechanism_switched_in_the_reference_fails_the_comparison(
        tokens, params, name, fresh_traces):
    patch, model = _switched(name)
    for attribute, value in patch.items():
        fresh_traces.setattr(reference, attribute, value)
    got, want = program_logits(params, tokens), reference_logits(params, tokens, model)
    assert not close(got, want, 50 * TIGHT), name


def test_the_balance_term_and_the_recipe_are_what_the_file_assumes():
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna_s_2.1.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "train_ep32_swa_resident_4k.json")) as f:
        mix = json.load(f)
    assert sorted(config["assumed"]) == [
        "balance_term", "hidden_act", "qk_norm", "recipe", "router_activation", "shared_expert_gate"]
    assert mix["train_config"]["aux_loss_weight"] == config["recipe"]["balance_alpha"] == 1e-3
    assert (mix["train_config"]["base_lr"], mix["train_config"]["weight_decay"], mix["train_config"]["clip_grad_norm"]) == (
        config["recipe"]["peak_learning_rate"], config["recipe"]["weight_decay"], config["recipe"]["clip_grad_norm"])


def program_loss(model):
    def loss(params, tokens):
        out, state = model.apply(
            {"params": params, "batch_stats": bias_rows()}, tokens[:, :-1], is_training=True, targets=tokens[:, 1:],
            mutable=["batch_stats", "losses"],
        )
        balance = sum(jnp.sum(x) for x in jax.tree.leaves(state["losses"]))
        return jnp.mean(out["ce"]) + ALPHA * balance, out
    return loss


@pytest.mark.parametrize("remat", [False, True])
def test_loss_every_gradient_leaf_and_the_counts_match_the_reference(tokens, params, remat):
    model = build(remat=remat)
    (loss, out), grads = jax.jit(jax.value_and_grad(program_loss(model), has_aux=True))(params, tokens)
    want_loss, want, counts = reference.make_loss_and_grad(model_file())(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= TIGHT * float(want_loss)
    assert "ce_mtp" not in out  # no module
    assert out["moe_counts"].shape == (BATCH, 4, EXPERTS)
    assert np.array_equal(np.asarray(jnp.sum(out["moe_counts"], axis=0)), np.asarray(counts))
    for key in ("attn_gate_mean", "attn_gate_mean_window", "attn_gate_mean_full"):
        assert out[key].shape == (BATCH,) and 0.3 < float(out[key][0]) < 0.7
    assert float(out["attn_gate_mean"][0]) == pytest.approx(
        (3 * float(out["attn_gate_mean_window"][0]) + 2 * float(out["attn_gate_mean_full"][0])) / 5, rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(want))
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        assert close(got, ref, 1e-4), weights.path_of(path)


# ------------------------------------------------------- the share of a layer


@pytest.mark.parametrize("mixer", ["sliding_attention", "full_attention"])
def test_the_thirty_two_shares_routed_parts_add_up_to_the_uncut_layer(mixer):
    """64 toy experts over 32 shares of 2 (the deployment's 32 chips a layer):
    the routed parts the shares give, with what every share computes alike
    (the token mixer, the residual and the gated shared expert) counted once,
    add up to the uncut reference's layer output."""
    shares, d, seq, experts = 32, 64, 32, 64
    sizes = dict(mlp_ch=32, num_experts=experts, top_k=TOP_K, routed_scale=2.5, norm_eps=1e-6, mixer=mixer,
                 mixer_sizes=SLIDING if mixer == "sliding_attention" else FULL,
                 scoring="softmax", shared_gate=True)
    whole = LatentDecoderBlock(**sizes, experts_held=None)
    x = jax.random.normal(jax.random.PRNGKey(40), (1, seq, d))
    bias = jnp.zeros((experts,))
    abstract = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, x, bias))["params"]
    p = weights.draw_params(abstract, 5)
    model = dict(model_file(), num_experts_published=experts, num_experts=experts)
    heads = SLIDING["num_heads"] if mixer == "sliding_attention" else FULL["num_heads"]
    with jax.default_matmul_precision("highest"):
        want, want_counts, _ = reference._layer(x[0], p, mixer, heads, True, reference._static(model))
        h = x[0] + reference.attention(
            reference.norm(x[0], p["attn_norm"], 1e-6), p["GatedSelfAttentionBlock_0"], mixer, heads, model)
        alike = h + reference.shared_part(reference.norm(h, p["ffn_norm"], 1e-6), p["moe"])
    total = alike  # what every share computes alike, once
    for share in range(shares):
        held = (2 * share, 2)
        cut = dict(p["moe"], experts=jax.tree.map(lambda leaf: leaf[2 * share:2 * share + 2], p["moe"]["experts"]))
        out, counts, _, _ = LatentDecoderBlock(**sizes, experts_held=held).apply({"params": {**p, "moe": cut}}, x, bias)
        total = total + (out[0] - alike)  # this share's routed part alone
        assert float(jnp.sum(counts)) == seq * TOP_K  # each share routes over all 64
        assert np.array_equal(np.asarray(counts[0]), np.asarray(want_counts))
    assert close(total, want, 5e-5)


# ------------------------------------------------- the task through the trainer


def _trainer(held):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    overrides = {**SIZES, "mixers": list(KINDS), "remat": True, "experts_held": list(held)}
    cfg = TrainConfig(
        model_name="laguna_s_2.1", num_classes=VOCAB, compute_dtype="float32",
        global_batch_size=BATCH, model_overrides=overrides,
        label_smoothing=0.0, warmup_epochs=0, base_lr=3e-4, lr_scaling_divisor=BATCH,
        weight_decay=0.1, aux_loss_weight=ALPHA, log_every_steps=1, fleet=False, transpose_images=False,
    )
    return Trainer(cfg, mesh=create_mesh({"data": 1}, devices=jax.devices()[:1]))


def test_fit_trains_the_family_and_three_updates_match_the_reference(tokens):
    held = (4, 8)
    batches = [jax.random.randint(jax.random.PRNGKey(20 + i), (BATCH, SEQ + 1), 0, VOCAB, jnp.int32) for i in range(3)]
    held_params = draw(build(experts_held=held), tokens)
    trainer = _trainer(held)
    state = trainer.init_state(0).replace(params=jax.tree.map(jnp.array, held_params))
    state, history = trainer.fit(iter({"tokens": np.asarray(b)} for b in batches), num_steps=3, state=state)
    logged = [h for h in history if "loss" in h]
    hp = {k: getattr(trainer.config, k) for k in (
        "base_lr", "global_batch_size", "lr_scaling_divisor", "num_train_images", "warmup_epochs",
        "num_epochs", "end_lr", "weight_decay", "clip_grad_norm",
    )}
    start = [np.asarray(leaf) for leaf in jax.tree.leaves(held_params)]
    want = reference.follow_steps(jax.tree.map(jnp.array, held_params), batches, hp, model_file(held))
    assert len(logged) == 3 and int(state.step) == 3
    for step, (m, loss) in enumerate(zip(logged, want["losses"])):
        # As test_lfm2.py: two losses on the seeded weights to float32's rounding, the third after an update in
        # which Adam has normalised every entry.
        assert abs(m["loss"] - loss) <= (TIGHT if step < 2 else 2e-4) * loss
        assert "loss_mtp" not in m
        assert m["loss"] == pytest.approx(m["loss_main"] + ALPHA * m["aux_loss"], rel=1e-5)
        assert 0.3 < m["attn_gate_mean_window"] < 0.7 and 0.3 < m["attn_gate_mean_full"] < 0.7
        assert 0.3 < m["moe_held_share"] < 0.7 and m["moe_bias_abs_max"] == 0.0
    assert np.array_equal(np.asarray(state.batch_stats["select_bias"]), np.zeros((4, EXPERTS)))  # no selection bias
    change = [np.asarray(a) - b for a, b in zip(jax.tree.leaves(state.params), start)]
    scale = max(float(np.max(np.abs(c))) for c in want["change"])
    assert scale > 1e-4  # the weights moved
    for got, ref in zip(change, want["change"]):
        off = np.abs(got - ref)
        assert float(np.linalg.norm(off)) <= 8e-2 * float(np.linalg.norm(ref))
        assert int(np.sum(off > 3e-2 * scale)) <= 2e-2 * off.size
