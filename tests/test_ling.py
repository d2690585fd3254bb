"""The Ling-3.0-flash family (Kimi delta attention five layers to one gated
direct-query latent-attention layer, sigmoid-routed experts picked inside a
few groups, a shared expert) against its plain float32 reference, at toy sizes
on the CPU: hidden 64, one period of six layers after one dense layer's FFN, 2
heads of 16 in the delta-rule blocks, 2 latent heads of 8 + 8 / 8 on a rank of
16, 32 experts in 4 groups of which 2 are kept and 4 experts chosen,
vocabulary 97, 32 positions.

Tolerances. Program and reference both compute in float32 here, in different
orders (the rule in chunks against one token at a time, a sort and grouped
matmuls against a loop over experts), so ``TIGHT`` = 2e-5 of the compared
tensor's largest entry, as ``test_joyai.py``, for a block, a layer and the
loss; the logits of the six-layer model are held to ``DEEP`` = 5e-5 (2.3e-5
read: two layers more than the four of ``test_qwen3_next.py``, whose logits
read 2e-5) and its gradients to 3e-4 (9.4e-5 read on the worst leaf, a norm's
weight under five recurrences; ``test_qwen3_next.py`` holds four layers to
1e-4).
The seeds leave the margin between the last chosen and the first unchosen
score above 1e-6 at every token."""

import gc
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
from benchmark.reference import ling as reference  # noqa: E402
from sav_tpu.models import create_model, model_task  # noqa: E402
from sav_tpu.models.joyai import MIXER_BLOCKS, LatentDecoderBlock, hybrid_mixers  # noqa: E402
from sav_tpu.models.layers import GatedFFBlock, LatentSelfAttentionBlock  # noqa: E402
from sav_tpu.models.layers.kda import KDABlock  # noqa: E402
from sav_tpu.models.layers.moe import _expert_ffn, _Router, kept_groups  # noqa: E402
from sav_tpu.models.registry import LING_EXPERT_LIMITS, LING_SHARED_LIMITS, _REGISTRY  # noqa: E402

TIGHT, DEEP = 2e-5, 5e-5
VOCAB, SEQ, BATCH, EXPERTS, TOP_K, GROUPS, KEPT, ALPHA = 97, 32, 2, 32, 4, 4, 2, 1e-3
KDA = {"heads": 2, "key_ch": 16, "value_ch": 16, "conv_width": 4, "lower_bound": -5.0, "chunk": 8}
LATENT = dict(num_heads=2, kv_rank=16, nope_ch=8, rope_ch=8, v_ch=8)
SIZES = dict(embed_dim=64, num_layers=6, first_dense=1, mlp_ch=48, expert_ch=32, num_experts=EXPERTS, top_k=TOP_K,
             n_group=GROUPS, topk_group=KEPT, loss_block_tokens=16, kda=KDA, **LATENT)


def model_file(held=(0, EXPERTS), layers=6, limits=None):
    """What ``benchmark/configs/ling_3.0_flash.json`` holds, at the toy sizes."""
    return {
        "hidden_size": 64, "num_layers": layers, "first_k_dense_replace": 1, "layer_group_size": 6,
        "intermediate_size": 48, "num_attention_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "v_head_dim": 8, "rope_theta": 6000000, "rms_norm_eps": 1e-6,
        "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
        "num_experts_published": EXPERTS, "expert_offset": held[0], "num_experts": held[1],
        "num_experts_per_tok": TOP_K, "n_group": GROUPS, "topk_group": KEPT, "routed_scaling_factor": 2.5,
        "expert_swiglu_limit_list": list(limits or (0,) * 42), "share_expert_swiglu_limit_list": list(limits or (0,) * 42),
        "vocab_size": VOCAB, "recipe": {"balance_alpha": ALPHA, "bias_update_rate": 1e-3},
    }


def build(dtype=jnp.float32, **overrides):
    return create_model("ling_3.0_flash", num_classes=VOCAB, dtype=dtype, **{**SIZES, **overrides})


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


def bias_rows(layers=5):
    return {"select_bias": jnp.zeros((layers, EXPERTS))}


# ------------------------------------------------------------------ the registry


def test_the_registry_entry_builds_the_published_model():
    cls, entry = _REGISTRY["ling_3.0_flash"]
    model = create_model("ling_3.0_flash", num_classes=157184)
    assert cls.__name__ == "JoyAILM" and model_task("ling_3.0_flash") == "tokens_mtp"
    assert model.num_layers == 42 and model.embed_dim == 2560 and model.first_dense == 2
    assert len(model.mixers) == 42 and model.mixers.count("kda") == 35 and model.mixers.count("latent") == 7
    assert all((kind == "latent") == ((i + 1) % 6 == 0) for i, kind in enumerate(model.mixers))
    assert model.mixers == hybrid_mixers(42, 6, full="latent", linear="kda") and MIXER_BLOCKS["kda"][0] is KDABlock
    assert (model.num_experts, model.top_k, model.n_group, model.topk_group) == (512, 8, 8, 4)
    assert model.routed_scale == 2.5 and model.scoring == "sigmoid" and model.shared_expert and not model.shared_gate
    assert model.q_rank is None and model.kv_rank == 512 and (model.nope_ch, model.rope_ch, model.v_ch) == (128, 64, 128)
    assert model.latent_qk_norm and model.latent_gate is True and model.rope_theta == 6e6
    assert dict(model.kda) == {"heads": 32, "key_ch": 128, "value_ch": 128, "conv_width": 4, "lower_bound": -5.0}
    assert model.mlp_ch == 6144 and model.expert_ch == 768 and model.mtp_modules == 0 and not model.tie_head
    # The config's two lists, a number a published layer.
    assert tuple(model.expert_limits) == LING_EXPERT_LIMITS == (0,) * 35 + (4,) * 7
    assert tuple(model.shared_limits) == LING_SHARED_LIMITS == (0,) * 34 + (5,) * 6 + (7,) * 2
    assert entry["bias_update_rate"] == 1e-3


def test_the_published_layers_are_thirty_five_to_seven_with_two_dense():
    """The abstract tree of the published depth at toy widths: every layer's
    mixer, its FFN's kind and its SwiGLU limits, from the registry alone."""
    wide = {k: v for k, v in SIZES.items() if k not in ("num_layers", "first_dense")}
    model = create_model("ling_3.0_flash", num_classes=VOCAB, **wide)
    tree = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32), is_training=False)
    )["params"]
    layers = [tree[f"layer_{i}"] for i in range(42)]
    assert sum("KDABlock_0" in layer for layer in layers) == 35
    assert [i for i, layer in enumerate(layers) if "LatentSelfAttentionBlock_0" in layer] == [5, 11, 17, 23, 29, 35, 41]
    assert [i for i, layer in enumerate(layers) if "GatedFFBlock_0" in layer] == [0, 1]
    assert all("moe" in layer and "shared" in layer["moe"] for layer in layers[2:])
    assert "lm_head" in tree and "mtp" not in tree  # an untied head, no module


# -------------------------------------------------------------------- the router


def written_out_selection(scores, bias, groups=GROUPS, kept=KEPT, k=TOP_K):
    """Group-limited top-k in numpy, a token at a time."""
    biased = np.asarray(scores, np.float64) + np.asarray(bias, np.float64)
    chosen, masks = [], []
    for row in biased:
        by_group = row.reshape(groups, -1)
        group_score = np.sort(by_group, axis=-1)[:, -2:].sum(-1)
        best = sorted(range(groups), key=lambda g: (-group_score[g], g))[:kept]
        allowed = np.full_like(row, -np.inf)
        for g in best:
            lo = g * by_group.shape[1]
            allowed[lo:lo + by_group.shape[1]] = row[lo:lo + by_group.shape[1]]
        chosen.append(sorted(range(len(row)), key=lambda e: (-allowed[e], e))[:k])
        masks.append([g in best for g in range(groups)])
    return np.array(chosen), np.array(masks)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_group_step_against_a_written_out_selection(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (24, 32))
    kernel = jax.random.normal(jax.random.PRNGKey(seed + 10), (32, EXPERTS)) * 32 ** -0.5
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 20), (EXPERTS,))
    router = _Router(EXPERTS, TOP_K, 2.5, "sigmoid", 0.0, GROUPS, KEPT)
    scores, chosen, weights_, kept = router.apply({"params": {"kernel": kernel}}, x, bias)
    want_scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(kernel, np.float64))))
    want_chosen, want_kept = written_out_selection(want_scores, bias)
    assert np.allclose(np.asarray(scores), want_scores, atol=1e-6)
    assert np.array_equal(np.asarray(chosen), want_chosen) and np.array_equal(np.asarray(kept), want_kept)
    assert np.all(np.asarray(kept).sum(-1) == KEPT)
    assert np.all(want_kept[np.arange(24)[:, None], want_chosen // (EXPERTS // GROUPS)])  # every choice in a kept group
    picked = np.take_along_axis(want_scores, want_chosen, axis=-1)  # norm_topk_prob without the bias, times 2.5
    assert np.allclose(np.asarray(weights_), 2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)
    ref_scores, ref_chosen, ref_weights = reference.route(x, {"route": {"kernel": kernel}}, bias, model_file())
    assert np.array_equal(np.asarray(ref_chosen), want_chosen) and np.allclose(np.asarray(ref_weights), np.asarray(weights_), atol=1e-6)
    assert np.array_equal(np.asarray(reference.groups_kept(scores + bias, GROUPS, KEPT)), want_kept)


def test_a_token_whose_best_experts_lie_in_more_groups_than_are_kept():
    """512 scores in 8 groups of 64, 4 kept, 8 chosen (the published sizes):
    one token's 8 best experts lie in 5 groups, so the plain top-8 is NOT what
    the group step selects; the fifth group's expert gives way to the best
    expert left in the four kept groups."""
    experts, groups, kept, k = 512, 8, 4, 8
    scores = np.full((2, experts), 0.1, np.float32)
    best = [0, 1, 64, 65, 128, 129, 192, 320]  # groups 0, 0, 1, 1, 2, 2, 3 and one alone in group 5
    scores[0, best] = [0.9, 0.8, 0.85, 0.75, 0.7, 0.65, 0.6, 0.95]
    scores[0, [193, 321]] = [0.5, 0.2]  # group 3 scores 0.6 + 0.5 = 1.1; group 5 scores 0.95 + 0.2 = 1.15
    scores[0, [2, 66]] = [0.3, 0.25]
    scores[1] = np.linspace(0.2, 0.8, experts)  # an ordinary row beside it
    mask = np.asarray(kept_groups(jnp.asarray(scores), groups, kept))
    assert mask[0].tolist() == [True, True, True, False, False, True, False, False]  # 1.7, 1.6, 1.35, 1.15 beat 1.1
    assert sorted(np.argsort(-scores[0])[:k].tolist()) == sorted(best)  # the plain top-8 spans five groups
    want_chosen, want_kept = written_out_selection(scores, np.zeros(experts), groups, kept, k)
    assert np.array_equal(want_kept, mask)
    # top_k of the masked row, through the router's own code path (an identity kernel on the logits).
    logits = np.log(scores / (1.0 - scores))
    router = _Router(experts, k, 2.5, "sigmoid", 0.0, groups, kept)
    _, chosen, _, _ = router.apply({"params": {"kernel": jnp.eye(experts)}}, jnp.asarray(logits), jnp.zeros((experts,)))
    assert np.array_equal(np.asarray(chosen), want_chosen)
    assert 192 not in np.asarray(chosen)[0] and 321 not in np.asarray(chosen)[0] and 2 in np.asarray(chosen)[0]
    assert set(np.asarray(chosen)[0] // 64) <= {0, 1, 2, 5}
    _, ref_chosen, _ = reference.route(
        jnp.asarray(logits), {"route": {"kernel": jnp.eye(experts)}}, jnp.zeros((experts,)),
        {**model_file(), "n_group": groups, "topk_group": kept, "num_experts_per_tok": k},
    )
    assert np.array_equal(np.asarray(ref_chosen), want_chosen)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_one_group_is_todays_router_bit_for_bit(scoring):
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    variables = {"params": {"kernel": jax.random.normal(jax.random.PRNGKey(2), (32, EXPERTS)) * 32 ** -0.5}}
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (EXPERTS,))
    today = _Router(EXPERTS, TOP_K, 2.5, scoring)
    grouped = _Router(EXPERTS, TOP_K, 2.5, scoring, 0.0, 1, 1)
    for got, want in zip(grouped.apply(variables, x, bias)[:3], today.apply(variables, x, bias)[:3]):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert str(jax.make_jaxpr(lambda: grouped.apply(variables, x, bias))()) == str(
        jax.make_jaxpr(lambda: today.apply(variables, x, bias))())
    assert grouped.apply(variables, x, bias)[3] is None  # no group step, no mask
    # ... and is the top-k of score plus bias written out.
    scores = today.apply(variables, x, bias)[0]
    assert np.array_equal(np.asarray(today.apply(variables, x, bias)[1]), np.asarray(jax.lax.top_k(scores + bias, TOP_K)[1]))


# --------------------------------------------------------------------- the clamp


def test_the_clamp_against_clip_written_out_and_none_at_zero():
    rows = 2.0 * jax.random.normal(jax.random.PRNGKey(0), (24, 16))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    kernels = tuple(jax.random.normal(k, shape) for k, shape in zip(ks, ((3, 16, 8), (3, 16, 8), (3, 8, 16))))
    sizes = jnp.array([10, 6, 8], jnp.int32)
    per_row = np.repeat(np.arange(3), np.asarray(sizes))

    def written(limit):
        gate = jnp.einsum("rd,rdf->rf", rows, kernels[0][per_row])
        up = jnp.einsum("rd,rdf->rf", rows, kernels[1][per_row])
        if limit:
            gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
        return jnp.einsum("rf,rfd->rd", jax.nn.silu(gate) * up, kernels[2][per_row])

    assert close(_expert_ffn(rows, kernels, sizes, None, 4.0), written(4.0), 1e-5)
    assert not close(written(4.0), written(0.0), 1e-2)  # the limit binds at these sizes
    # A limit of 0 is the function as it was: the same numbers and the same program.
    assert np.array_equal(np.asarray(_expert_ffn(rows, kernels, sizes, None, 0.0)), np.asarray(_expert_ffn(rows, kernels, sizes, None)))
    assert close(_expert_ffn(rows, kernels, sizes, None), written(0.0), 1e-5)
    assert str(jax.make_jaxpr(lambda: _expert_ffn(rows, kernels, sizes, None, 0.0))()) == str(
        jax.make_jaxpr(lambda: _expert_ffn(rows, kernels, sizes, None))())
    # The shared expert's block, against the reference's clamp.
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (12, 16))
    block = GatedFFBlock(hidden_ch=8, limit=5.0)
    p = block.init(jax.random.PRNGKey(3), x)["params"]
    p = jax.tree.map(lambda leaf: 4.0 * leaf, p)
    assert close(block.apply({"params": p}, x), reference.mlp(x, p, 5.0), 1e-5)
    assert close(GatedFFBlock(hidden_ch=8).apply({"params": p}, x), reference.mlp(x, p, 0.0), 1e-5)
    assert not close(block.apply({"params": p}, x), reference.mlp(x, p, 0.0), 1e-2)


def test_a_clamped_layer_of_the_model_matches_the_reference(tokens):
    """Toy depth 6 with a limit on four of its layers, small enough to bind
    at the seeded weights (the routed experts' branches have a deviation of
    0.16 there, the shared expert's of 1): program and reference read the
    limit by layer index."""
    limits = (0, 0.1, 0, 0.5, 0.3, 0.1) + (0,) * 36
    model = build(expert_limits=limits, shared_limits=limits)
    p = draw(model, tokens, seed=4)
    got = model.apply({"params": p, "batch_stats": bias_rows()}, tokens[:, :-1], is_training=False)["logits"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([
            reference.sequence_logits(p, jnp.zeros((5, EXPERTS)), row[:-1], model_file(limits=limits)) for row in tokens])
        plain = jnp.stack([reference.sequence_logits(p, jnp.zeros((5, EXPERTS)), row[:-1], model_file()) for row in tokens])
    assert close(got, want, DEEP) and not close(plain, want, 1e-2)


# ------------------------------------------------------------ the two token mixers


def test_the_latent_block_with_a_direct_query_norms_and_gate(params):
    x = jax.random.normal(jax.random.PRNGKey(5), (BATCH, SEQ, 64))
    p = params["layer_5"]["LatentSelfAttentionBlock_0"]
    assert sorted(p["to_qkv"]) == ["gate", "k_rope_norm", "kv_a", "kv_b", "kv_norm", "q", "q_head_norm"]
    assert p["to_qkv"]["q"]["kernel"].shape == (64, 2 * 16) and p["to_qkv"]["gate"]["kernel"].shape == (64, 2)
    assert p["to_qkv"]["q_head_norm"]["scale"].shape == (16,) and p["to_qkv"]["k_rope_norm"]["scale"].shape == (8,)
    block = LatentSelfAttentionBlock(q_rank=None, qk_norm=True, gate=True, rope_theta=6e6, **LATENT)
    got = block.apply({"params": p}, x)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.gated_latent_attention(row, p, model_file()) for row in x])
    assert close(got, want)
    ungated = LatentSelfAttentionBlock(q_rank=None, qk_norm=True, rope_theta=6e6, **LATENT)
    rest = {**p, "to_qkv": {k: v for k, v in p["to_qkv"].items() if k != "gate"}}
    assert not close(got, ungated.apply({"params": rest}, x))  # the gate is in the result


@pytest.mark.parametrize("name", ["joyai_llm_flash", "xing4_0_29b_a4b"])
def test_the_low_rank_query_blocks_are_as_they_were(name):
    """JoyAI's and Xing's latent block: the low-rank query with its norm, no
    norm a head, no gate; and the program of the defaults is the program of
    the arguments spelt out."""
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 64))
    entry = _REGISTRY[name][1]
    assert entry["q_rank"] and "latent_gate" not in entry and "latent_qk_norm" not in entry
    block = LatentSelfAttentionBlock(q_rank=24, **LATENT)
    spelt = LatentSelfAttentionBlock(q_rank=24, qk_norm=False, gate=False, **LATENT)
    tree = block.init(jax.random.PRNGKey(0), x)["params"]
    assert sorted(tree["to_qkv"]) == ["kv_a", "kv_b", "kv_norm", "q_a", "q_b", "q_norm"]
    assert str(jax.make_jaxpr(lambda: block.apply({"params": tree}, x))()) == str(
        jax.make_jaxpr(lambda: spelt.apply({"params": tree}, x))())


def test_the_kda_block_against_the_reference(params):
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, 64))
    p = params["layer_1"]["KDABlock_0"]
    assert sorted(p) == ["A_log", "conv", "dt_bias", "gate_norm", "to_out", "to_qkv"]
    assert sorted(p["to_qkv"]) == ["b", "f", "g", "k", "q", "v"] and p["dt_bias"].shape == (32,) and p["A_log"].shape == (2,)
    got, stats = KDABlock(**KDA).apply({"params": p}, x)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.kda_block(row, p, model_file()) for row in x])
        gate = jnp.stack([
            reference.kda_gate(row @ p["to_qkv"]["f"]["kernel"], p["A_log"], p["dt_bias"], -5.0) for row in x])
    assert close(got, want)
    assert -5.0 < float(stats["decay_min"]) < 0.0 and np.isclose(float(stats["decay_min"]), float(jnp.min(gate)), atol=1e-6)
    assert float(stats["state_rms_max"]) > 0.0
    # The gate drives every lane to the bound and the block stays finite.
    pushed = dict(p, dt_bias=jnp.full_like(p["dt_bias"], 40.0))
    out, pushed_stats = KDABlock(**KDA).apply({"params": pushed}, x)
    assert float(pushed_stats["decay_min"]) == -5.0 and bool(jnp.all(jnp.isfinite(out)))
    with jax.default_matmul_precision("highest"):
        assert close(out, jnp.stack([reference.kda_block(row, pushed, model_file()) for row in x]))


def test_the_kda_blocks_initialiser_puts_the_paper_s_dt_through_the_gates_inverse():
    fresh = KDABlock(**KDA).init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))["params"]
    dt = 5.0 * jax.nn.sigmoid(fresh["dt_bias"])  # at A_log 0 and a 0 a lane decays by exp(-dt) a token
    assert 1e-3 <= float(jnp.min(dt)) and float(jnp.max(dt)) <= 1e-1 + 1e-6
    assert float(jnp.min(jnp.exp(fresh["A_log"]))) > 0.0 and float(jnp.max(jnp.exp(fresh["A_log"]))) <= 16.0


# ------------------------------------------------------- model against reference


def test_the_tree_is_the_one_the_reference_reads(params):
    config = {**model_file((0, EXPERTS)), "vocab_size": VOCAB}
    reference.check_layout(params, config)
    assert sorted(params) == ["embed", "final_norm"] + [f"layer_{i}" for i in range(6)] + ["lm_head"]
    assert sorted(params["layer_0"]) == ["GatedFFBlock_0", "KDABlock_0", "attn_norm", "ffn_norm"]
    assert sorted(params["layer_1"]) == ["KDABlock_0", "attn_norm", "ffn_norm", "moe"]
    assert sorted(params["layer_5"]) == ["LatentSelfAttentionBlock_0", "attn_norm", "ffn_norm", "moe"]
    assert sorted(params["layer_1"]["moe"]) == ["experts", "route", "shared"]
    with pytest.raises(ValueError, match="not the configuration's"):
        reference.check_layout(params, {**config, "num_experts": 8})
    with pytest.raises(ValueError, match="not the configuration's"):
        reference.check_layout(params, {**config, "layer_group_size": 3})


def test_logits_match_the_reference(tokens, params):
    model = build()
    got = jax.jit(lambda p, t: model.apply({"params": p, "batch_stats": bias_rows()}, t, is_training=False))(
        params, tokens[:, :-1])["logits"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.sequence_logits(params, jnp.zeros((5, EXPERTS)), row[:-1], model_file()) for row in tokens])
    assert got.shape == (BATCH, SEQ, VOCAB) and close(got, want, DEEP)


def program_loss(model, alpha=ALPHA):
    def loss(params, tokens):
        out, state = model.apply(
            {"params": params, "batch_stats": bias_rows()}, tokens[:, :-1], is_training=True,
            targets=tokens[:, 1:], mutable=["batch_stats", "losses"],
        )
        balance = sum(jnp.sum(x) for x in jax.tree.leaves(state["losses"]))
        return jnp.mean(out["ce"]) + alpha * balance, (out, state)
    return loss


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradients_match_the_reference(tokens, params, remat):
    model = build(remat=remat)
    (loss, (out, state)), grads = jax.jit(jax.value_and_grad(program_loss(model), has_aux=True))(params, tokens)
    bias = jnp.zeros((5, EXPERTS))
    want_loss, want, counts = reference.make_loss_and_grad(model_file())(params, bias, tokens)
    assert abs(float(loss) - float(want_loss)) <= TIGHT * float(want_loss)
    assert "ce_mtp" not in out  # no module
    assert np.array_equal(np.asarray(jnp.sum(out["moe_counts"], axis=0)), np.asarray(counts))
    # The selection bias's step, on the step's counts.
    want_bias = reference.stepped_bias(bias, counts, 1e-3)
    assert np.array_equal(np.asarray(state["batch_stats"]["select_bias"]), np.asarray(want_bias))
    for name in ("kda_decay_min", "kda_state_rms_max", "moe_groups_held"):
        assert out[name].shape == (BATCH,) and float(out[name][0]) == float(out[name][1])
    assert -5.0 < float(out["kda_decay_min"][0]) < 0.0
    assert float(out["moe_groups_held"][0]) == 1.0  # every expert held: every token's groups reach them
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        assert close(got, ref, 3e-4), weights.path_of(path)


def test_the_share_of_tokens_whose_groups_reach_the_experts_held(tokens, params):
    """Experts 0-3 of 32 held: half of group 0. ``moe_groups_held`` is the
    share of tokens that kept group 0, counted from the reference's groups."""
    held = (0, 4)
    cut = jax.tree.map(lambda x: x, params)
    for i in range(1, 6):
        cut[f"layer_{i}"]["moe"]["experts"] = jax.tree.map(lambda leaf: leaf[:4], params[f"layer_{i}"]["moe"]["experts"])
    model = build(experts_held=held)
    out = model.apply({"params": cut, "batch_stats": bias_rows()}, tokens[:, :-1], is_training=False, targets=tokens[:, 1:])
    shares = []
    h = [params["embed"]["embedding"][row[:-1]] for row in tokens]
    file = model_file(held)
    with jax.default_matmul_precision("highest"):
        for i in range(6):
            kept = []
            for b in range(BATCH):
                if i:
                    x = reference.norm(h[b] + (
                        reference.gated_latent_attention if i == 5 else reference.kda_block
                    )(reference.norm(h[b], cut[f"layer_{i}"]["attn_norm"], 1e-6),
                      cut[f"layer_{i}"]["LatentSelfAttentionBlock_0" if i == 5 else "KDABlock_0"], file),
                        cut[f"layer_{i}"]["ffn_norm"], 1e-6)
                    scores = jax.nn.sigmoid(x @ cut[f"layer_{i}"]["moe"]["route"]["kernel"])
                    kept.append(np.asarray(reference.groups_kept(scores, GROUPS, KEPT))[:, 0])
                h[b], _, _ = reference.layer(h[b], cut[f"layer_{i}"], jnp.zeros((EXPERTS,)) if i else None, i, file)
            if kept:
                shares.append(np.mean(np.concatenate(kept)))
    assert 0.2 < float(out["moe_groups_held"][0]) < 0.8
    assert np.isclose(float(out["moe_groups_held"][0]), np.mean(shares), atol=1e-6)


# ------------------------------------------------------- the share of a layer


@pytest.mark.parametrize("mixer", ["kda", "latent"])
def test_the_eight_shares_parts_add_up_to_the_uncut_layer(mixer):
    """32 toy experts in 4 groups over 8 shares of 4 (every share an eighth
    of the layer, half a group: the deployment's 64 shares are an eighth of a
    group each, with the same ``n_group`` / ``topk_group`` step): the routed
    parts the shares give, with the shared expert and the token mixer counted
    once, add up to the uncut reference's layer output. The mixer, the router
    with its groups and the shared expert are replicated: every share
    computes them alike."""
    shares, each, d, seq = 8, 4, 64, 32
    sizes = dict(mlp_ch=32, num_experts=EXPERTS, top_k=TOP_K, routed_scale=2.5, norm_eps=1e-6, rope_theta=6e6,
                 n_group=GROUPS, topk_group=KEPT, q_rank=None, latent_qk_norm=True, latent_gate=True,
                 mixer=mixer, mixer_sizes=KDA if mixer == "kda" else None, **LATENT)
    whole = LatentDecoderBlock(**sizes, experts_held=None)
    x = jax.random.normal(jax.random.PRNGKey(40), (1, seq, d))
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(41), (EXPERTS,))
    abstract = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, x, bias))["params"]
    p = weights.draw_params(abstract, 5)
    model = model_file()
    index = 1 if mixer == "kda" else 5  # the reference takes the kind from the layer's index
    with jax.default_matmul_precision("highest"):
        want, want_counts, _ = reference.layer(x[0], p, bias, index, model)
        # What every share computes alike, once: the layer with NO routed expert's part.
        mix = reference.kda_block if mixer == "kda" else reference.gated_latent_attention
        block = "KDABlock_0" if mixer == "kda" else "LatentSelfAttentionBlock_0"
        h = x[0] + mix(reference.norm(x[0], p["attn_norm"], 1e-6), p[block], model)
        shared = reference.mlp(reference.norm(h, p["ffn_norm"], 1e-6), p["moe"]["shared"])
    total = h + shared
    for share in range(shares):
        held = (share * each, each)
        cut = dict(p["moe"], experts=jax.tree.map(lambda leaf: leaf[held[0]:held[0] + each], p["moe"]["experts"]))
        out, counts, _, stats = LatentDecoderBlock(**sizes, experts_held=held).apply({"params": {**p, "moe": cut}}, x, bias)
        total = total + (out[0] - h - shared)  # this share's routed part alone
        assert float(jnp.sum(counts)) == seq * TOP_K  # each share routes over all 32
        assert np.array_equal(np.asarray(counts[0]), np.asarray(want_counts))
        assert 0.0 < float(stats["moe_groups_held"]) < 1.0
    assert close(total, want, 5e-5)


# ------------------------------------------------- the task through the trainer


def _trainer(held):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    overrides = {**SIZES, "remat": True, "experts_held": list(held)}
    cfg = TrainConfig(
        model_name="ling_3.0_flash", num_classes=VOCAB, compute_dtype="float32",
        global_batch_size=BATCH, model_overrides=overrides,
        label_smoothing=0.0, warmup_epochs=0, base_lr=3e-4, lr_scaling_divisor=BATCH,
        weight_decay=0.1, aux_loss_weight=ALPHA, log_every_steps=1, fleet=False, transpose_images=False,
    )
    return Trainer(cfg, mesh=create_mesh({"data": 1}, devices=jax.devices()[:1]))


def test_fit_trains_the_family_and_three_updates_match_the_reference(tokens):
    held = (8, 8)  # group 1 of 4
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
        # The first update runs at rate 0: two losses on the seeded weights, to float32's rounding. The third
        # follows an update in which Adam has normalised every entry, the ones whose gradient is all rounding
        # too: 2e-4, as test_qwen3_next.py.
        assert abs(m["loss"] - loss) <= (TIGHT if step < 2 else 2e-4) * loss
        assert "loss_mtp" not in m
        assert m["loss"] == pytest.approx(m["loss_main"] + ALPHA * m["aux_loss"], rel=1e-5)
        assert -5.0 < m["kda_decay_min"] < 0.0 and m["kda_state_rms_max"] > 0.0
        assert 0.2 < m["moe_groups_held"] < 0.9 and 0.1 < m["moe_held_share"] < 0.5
        assert m["moe_bias_abs_max"] == pytest.approx(1e-3 * (step + 1), rel=1e-5)
    # The bias's three steps, on the counts of the first two steps (before the weights move).
    bias = np.zeros((5, EXPERTS), np.float32)
    for counts in want["counts"]:
        bias = np.asarray(reference.stepped_bias(jnp.asarray(bias), jnp.asarray(counts), 1e-3))
    assert np.allclose(want["select_bias"], bias, atol=1e-7)
    got_bias = np.asarray(state.batch_stats["select_bias"])
    assert float(np.mean(np.abs(got_bias - want["select_bias"]) > 1e-6)) <= 0.02  # a count on a tie of the third step
    change = [np.asarray(a) - b for a, b in zip(jax.tree.leaves(state.params), start)]
    scale = max(float(np.max(np.abs(c))) for c in want["change"])
    assert scale > 1e-4  # the weights moved
    for got, ref in zip(change, want["change"]):
        # As test_qwen3_next.py holds a followed step: a leaf by its norm, 8e-2 of the change's own, and by the
        # share of entries off by more than 3e-2 of the largest change, 2e-2 (Adam moves an entry whose gradient
        # is all rounding by the rate with the rounding's sign); a leaf left out, scaled or decayed wrongly reads 1.
        off = np.abs(got - ref)
        assert float(np.linalg.norm(off)) <= 8e-2 * float(np.linalg.norm(ref))
        assert int(np.sum(off > 3e-2 * scale)) <= 2e-2 * off.size
