"""The LFM2 family (double-gated short convolutions with a grouped-query
softmax layer between them, sigmoid-routed experts with a selection bias and
NO shared expert, leading dense layers, a tied head) against its plain float32
reference, at toy sizes on the CPU: hidden 64, five layers in the cut's order
(conv + dense SwiGLU, then attention, conv, conv, conv on expert layers), 4
query heads of 16 on 1 key/value head, convolution 3, 16 experts of which 4 a
token, vocabulary 97, 32 positions.

Tolerances. Program and reference both compute in float32 here, in different
orders (a fused convolution against three shifted products, a sort and
grouped matmuls against a loop over experts, one table read twice against the
same), so ``TIGHT`` = 2e-5 of the compared tensor's largest entry, as
``test_joyai.py``; the gradients of a five-layer model are held to 1e-4. The
seeds leave the margin between the 4th and the 5th routing score above 1e-6
at every token."""

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
from benchmark.reference import lfm2 as reference  # noqa: E402
from sav_tpu.models import create_model, model_task  # noqa: E402
from sav_tpu.models.joyai import KEPT_UNDER_REMAT, KEPT_UNDER_REMAT_BESIDE_CONVOLUTION, LatentDecoderBlock  # noqa: E402
from sav_tpu.models.layers.gated_attention import GatedSelfAttentionBlock  # noqa: E402
from sav_tpu.models.layers.moe import _Router, gmm_tiling  # noqa: E402
from sav_tpu.models.registry import _REGISTRY  # noqa: E402
from sav_tpu.ops import attention as attention_ops  # noqa: E402

flash = importlib.import_module("sav_tpu.ops.flash_attention")  # the package exports the function under that name

TIGHT = 2e-5
VOCAB, SEQ, BATCH, EXPERTS, TOP_K, ALPHA, GAMMA = 97, 32, 2, 16, 4, 1e-4, 1e-3
KINDS = ("conv", "full_attention", "conv", "conv", "conv")  # published layers 1-5
ATTENTION = {"num_heads": 4, "kv_heads": 1, "head_ch": 16, "rotary_ch": 16, "gate": False}
SIZES = dict(embed_dim=64, num_layers=5, first_dense=1, mixers=KINDS, mlp_ch=96, expert_ch=32,
             num_experts=EXPERTS, top_k=TOP_K, loss_block_tokens=16, gated_attention=ATTENTION)


def model_file(held=(0, EXPERTS)):
    """What ``benchmark/configs/lfm2_24b_a2b.json`` holds, at the toy sizes."""
    return {
        "hidden_size": 64, "num_layers": 5, "first_k_dense_replace": 1, "layer_types_held": list(KINDS),
        "num_attention_heads": 4, "num_key_value_heads": 1, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "conv_L_cache": 3,
        "intermediate_size": 96, "moe_intermediate_size": 32, "routed_scaling_factor": 1,
        "num_experts_published": EXPERTS, "expert_offset": held[0], "num_experts": held[1],
        "num_experts_per_tok": TOP_K, "vocab_size": VOCAB,
        "recipe": {"balance_alpha": ALPHA, "bias_update_rate": GAMMA},
    }


def build(dtype=jnp.float32, **overrides):
    return create_model("lfm2_24b_a2b", num_classes=VOCAB, dtype=dtype, **{**SIZES, **overrides})


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


def bias_rows(value=None):
    return {"select_bias": jnp.zeros((4, EXPERTS)) if value is None else value}


# ------------------------------------------------------------- the registry


def test_the_registry_builds_the_published_forty_layers():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2_24b_a2b.json")) as f:
        source = json.load(f)
    cls, registered = _REGISTRY["lfm2_24b_a2b"]
    assert tuple(registered["mixers"]) == tuple(source["layer_types"]) and len(registered["mixers"]) == 40
    assert registered["mixers"].count("conv") == 30 and registered["mixers"].count("full_attention") == 10
    assert (registered["num_layers"], registered["first_dense"]) == (40, source["num_dense_layers_published"]) == (40, 2)
    assert (registered["num_experts"], registered["top_k"]) == (64, 4)
    assert registered["shared_expert"] is False and registered["tie_head"] is True and registered["mtp_modules"] == 0
    assert registered["kept_under_remat"] == KEPT_UNDER_REMAT_BESIDE_CONVOLUTION
    assert set(KEPT_UNDER_REMAT) <= set(KEPT_UNDER_REMAT_BESIDE_CONVOLUTION)
    assert model_task("lfm2_24b_a2b") == "tokens_mtp"
    assert gmm_tiling(2048, 1536) == (256, 1024, 768)  # the rule at this family's expert
    tree = jax.eval_shape(lambda: create_model("lfm2_24b_a2b", num_classes=64).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32), is_training=False))["params"]
    kinds = ["conv" if "ShortConvBlock_0" in tree[f"layer_{i}"] else "full_attention" for i in range(40)]
    assert kinds == source["layer_types"]
    assert [i for i in range(40) if "GatedFFBlock_0" in tree[f"layer_{i}"]] == [0, 1]
    assert "lm_head" not in tree and all("shared" not in tree[f"layer_{i}"]["moe"] for i in range(2, 40))
    # Qwen3-Next's entry gives the tuple it had: three delta-rule layers to one softmax layer.
    from sav_tpu.models.joyai import hybrid_mixers
    assert _REGISTRY["qwen3_next_80b_a3b"][1]["full_attention_interval"] == 4
    assert hybrid_mixers(8, 4) == ("gated_delta",) * 3 + ("gated_attention",) + ("gated_delta",) * 3 + ("gated_attention",)


def test_a_mixers_list_must_cover_the_layers_and_takes_no_module():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="token mixers for"):
        build(mixers=KINDS[:3]).init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False)
    with pytest.raises(ValueError, match="multi-token"):
        build(mtp_modules=1).init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False)
    # A depth cut runs the first num_layers of the list.
    tree = jax.eval_shape(lambda: build(num_layers=2).init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False))
    assert "GatedSelfAttentionBlock_0" in tree["params"]["layer_1"] and "layer_2" not in tree["params"]


# ------------------------------------------------- heads of 64 on a quarter


def grouped_operands(length, heads=4, kv_heads=1, dim=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, length, heads, dim))
    k = jax.random.normal(ks[1], (2, length, kv_heads, dim))
    v = jax.random.normal(ks[2], (2, length, kv_heads, dim))
    return q, k, v, jax.random.normal(ks[3], (2, length, heads, dim))


@pytest.mark.parametrize("length,blocks", [(256, dict(block_q=128, block_kv=128, block_b=1)), (200, {})],
                         ids=["whole_blocks", "ragged"])
def test_heads_of_64_on_a_quarter_as_many_run_head_major_and_match_the_dense_path(length, blocks):
    """A head of 64 is half a lane tile: the flash kernels take it head-major
    (lanes padded to 128, k and v repeated), whatever the blocks. Values, dq,
    and dk / dv summed over the group of four against the dense path, float32,
    in the interpreter: 2e-5 of the largest entry (dk and dv add four heads'
    roundings: 5e-5)."""
    q, k, v, g = grouped_operands(length)
    assert flash.layout_form(length, length, 64, 64, batch_heads=8, itemsize=4, **blocks) == "head_major"
    kernels = lambda q, k, v: flash.flash_attention(q, k, v, causal=True, interpret=True, **blocks)
    dense = lambda q, k, v: attention_ops.dot_product_attention(
        q, k, v, causal=True, backend="xla", logits_dtype=jnp.float32)
    out, pull = jax.vjp(jax.jit(kernels), q, k, v)
    want, want_pull = jax.vjp(dense, q, k, v)
    assert out.shape == q.shape and close(out, want)
    for name, got, ref in zip("dq dk dv".split(), pull(g), want_pull(g)):
        assert got.shape == ref.shape and close(got, ref, 5e-5), name


def test_the_block_without_a_gate_through_the_kernels_and_its_dispatch_line():
    """The grouped-query block at this family's form (no gate half in W_q,
    plain norm weights, rotary on the whole head), heads of 64 on a quarter as
    many: the kernels in the interpreter against the dense path, and what the
    dispatch log says of the call."""
    sizes = dict(num_heads=4, kv_heads=1, head_ch=64, rotary_ch=64, gate=False, norm_offset=False,
                 rope_theta=1e6, norm_eps=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 256, 64))
    dense_block = GatedSelfAttentionBlock(**sizes, backend="xla", logits_dtype=jnp.float32)
    p = dense_block.init(jax.random.PRNGKey(9), x)["params"]
    assert jax.tree.map(lambda leaf: leaf.shape, p) == {
        "to_qkv": {"q": {"kernel": (64, 256)}, "k": {"kernel": (64, 64)}, "v": {"kernel": (64, 64)},
                   "q_norm": {"scale": (64,)}, "k_norm": {"scale": (64,)}},
        "to_out": {"kernel": (4, 64, 64)},
    }
    want, stats = dense_block.apply({"params": p}, x)
    assert stats == {}  # no gate, no gate_mean
    attention_ops.clear_dispatch_log()
    got, _ = GatedSelfAttentionBlock(**sizes, backend="pallas").apply({"params": p}, x)
    (note,) = attention_ops.snapshot_dispatch_log()
    assert note["shape"] == [2, 256, 4, 64] and note["kv_heads"] == 1 and note["backend"] == "pallas"
    assert (note["layout"], note["grouped_kv"]) == ("head_major", "repeated")
    assert close(got, want)


def test_the_router_adds_its_eps_to_the_selected_sum():
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    kernel = jax.random.normal(jax.random.PRNGKey(2), (32, EXPERTS)) * 32 ** -0.5
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (EXPERTS,))
    scores, chosen, got, _ = _Router(EXPERTS, TOP_K, 1.0, "sigmoid", 1e-6).apply({"params": {"kernel": kernel}}, x, bias)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(kernel, np.float64))))
    order = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1)[:, :TOP_K]  # by score + bias
    picked = np.take_along_axis(s, order, axis=-1)  # weighted by the score alone
    assert np.array_equal(np.asarray(chosen), order)
    assert np.allclose(np.asarray(got), picked / (picked.sum(-1, keepdims=True) + 1e-6), atol=1e-6)
    _, ref_chosen, ref_weights = reference.route(x, {"route": {"kernel": kernel}}, bias, model_file())
    assert np.array_equal(np.asarray(ref_chosen), order) and np.allclose(np.asarray(ref_weights), np.asarray(got), atol=1e-6)


# ------------------------------------------------------- model against reference


def test_the_tree_is_the_one_the_reference_reads(params):
    config = model_file()
    reference.check_layout(params, config)
    assert sorted(params) == ["embed", "final_norm", "layer_0", "layer_1", "layer_2", "layer_3", "layer_4"]
    assert sorted(params["layer_0"]) == ["GatedFFBlock_0", "ShortConvBlock_0", "attn_norm", "ffn_norm"]
    assert sorted(params["layer_1"]) == ["GatedSelfAttentionBlock_0", "attn_norm", "ffn_norm", "moe"]
    assert sorted(params["layer_2"]) == ["ShortConvBlock_0", "attn_norm", "ffn_norm", "moe"]
    assert sorted(params["layer_1"]["moe"]) == ["experts", "route"] and list(params["final_norm"]) == ["scale"]
    with pytest.raises(ValueError, match="not the configuration's"):
        reference.check_layout(params, {**config, "num_experts": 8})
    with pytest.raises(ValueError, match="not the configuration's"):
        reference.check_layout(params, {**config, "layer_types_held": ["conv"] * 5})


def test_logits_match_the_reference(tokens, params):
    model = build()
    got = jax.jit(lambda p, t: model.apply({"params": p, "batch_stats": bias_rows()}, t, is_training=False))(
        params, tokens[:, :-1])["logits"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([
            reference.sequence_logits(params, jnp.zeros((4, EXPERTS)), row[:-1], model_file()) for row in tokens
        ])
    assert got.shape == (BATCH, SEQ, VOCAB) and close(got, want)


def program_loss(model, bias=None):
    def loss(params, tokens):
        out, state = model.apply(
            {"params": params, "batch_stats": bias_rows(bias)}, tokens[:, :-1], is_training=True,
            targets=tokens[:, 1:], mutable=["batch_stats", "losses"],
        )
        balance = sum(jnp.sum(x) for x in jax.tree.leaves(state["losses"]))
        return jnp.mean(out["ce"]) + ALPHA * balance, (out, state)
    return loss


@pytest.mark.parametrize("remat", [False, True])
def test_loss_gradients_counts_and_the_bias_step_match_the_reference(tokens, params, remat):
    model = build(remat=remat)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(12), (4, EXPERTS))  # a bias that changes selections
    (loss, (out, state)), grads = jax.jit(jax.value_and_grad(program_loss(model, bias), has_aux=True))(params, tokens)
    want_loss, want, counts = reference.make_loss_and_grad(model_file())(params, bias, tokens)
    assert abs(float(loss) - float(want_loss)) <= TIGHT * float(want_loss)
    assert "ce_mtp" not in out and "attn_gate_mean" not in out  # no module, no gate
    assert np.array_equal(np.asarray(jnp.sum(out["moe_counts"], axis=0)), np.asarray(counts))
    assert np.array_equal(
        np.asarray(state["batch_stats"]["select_bias"]), np.asarray(reference.stepped_bias(bias, counts, GAMMA)))
    assert out["sconv_out_rms_max"].shape == (BATCH,) and float(out["sconv_out_rms_max"][0]) > 0.0
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        assert close(got, ref, 1e-4), weights.path_of(path)


def test_the_tied_tables_gradient_is_the_sum_of_the_embeddings_and_the_heads(tokens, params):
    """The same weights in an untied model (the head's own leaf set to the
    table's transpose): the tied table's gradient is the embedding's plus the
    transpose of the head's, and the logits are the same."""
    tied, untied = build(), build(tie_head=False)
    both = dict(params, lm_head={"kernel": params["embed"]["embedding"].T})
    assert "lm_head" in jax.eval_shape(
        lambda: untied.init({"params": jax.random.PRNGKey(0)}, tokens[:, :-1], is_training=False))["params"]
    (loss, _), grads = jax.jit(jax.value_and_grad(program_loss(tied), has_aux=True))(params, tokens)
    (loss_untied, _), parts = jax.jit(jax.value_and_grad(program_loss(untied), has_aux=True))(both, tokens)
    assert float(loss) == pytest.approx(float(loss_untied), rel=1e-6)
    summed = parts["embed"]["embedding"] + parts["lm_head"]["kernel"].T
    assert float(jnp.max(jnp.abs(parts["lm_head"]["kernel"]))) > 0.0
    assert close(grads["embed"]["embedding"], summed, 1e-5)


# ------------------------------------------------------- the share of a layer


@pytest.mark.parametrize("mixer", ["conv", "full_attention"])
def test_the_eight_shares_routed_parts_add_up_to_the_uncut_layer(mixer):
    """16 toy experts over 8 shares of 2 (the deployment's 8 chips a layer):
    the routed parts the shares give, with what every share computes alike
    counted once, add up to the uncut reference's layer output. Here that is
    the token mixer and the residual alone: the family has NO shared expert,
    so a share's expert layer IS its routed part."""
    shares, d, seq = 8, 64, 32
    sizes = dict(mlp_ch=32, num_experts=EXPERTS, top_k=TOP_K, routed_scale=1.0, norm_eps=1e-5, rope_theta=1e6,
                 mixer=mixer, mixer_sizes={"conv_width": 3} if mixer == "conv" else ATTENTION,
                 shared_expert=False, router_weight_eps=1e-6)
    whole = LatentDecoderBlock(**sizes, experts_held=None)
    x = jax.random.normal(jax.random.PRNGKey(40), (1, seq, d))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(41), (EXPERTS,))
    abstract = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, x, bias))["params"]
    p = weights.draw_params(abstract, 5)
    assert sorted(p["moe"]) == ["experts", "route"]
    model = model_file()
    with jax.default_matmul_precision("highest"):
        want, want_counts, _ = reference.layer(x[0], p, bias, mixer, model)
        normed = reference.norm(x[0], p["attn_norm"], 1e-5)
        if mixer == "conv":
            h = x[0] + reference.short_conv(normed, p["ShortConvBlock_0"])
        else:
            h = x[0] + reference.attention(normed, p["GatedSelfAttentionBlock_0"], 4, 1, 1e6, 1e-5)
    total = h  # what every share computes alike, once: no routed expert's part, and no shared expert
    for share in range(shares):
        held = (2 * share, 2)
        cut = dict(p["moe"], experts=jax.tree.map(lambda leaf: leaf[2 * share:2 * share + 2], p["moe"]["experts"]))
        out, counts, _, _ = LatentDecoderBlock(**sizes, experts_held=held).apply({"params": {**p, "moe": cut}}, x, bias)
        total = total + (out[0] - h)  # this share's routed part alone
        assert float(jnp.sum(counts)) == seq * TOP_K  # each share routes over all 16
        assert np.array_equal(np.asarray(counts[0]), np.asarray(want_counts))
    assert close(total, want, 5e-5)


# ------------------------------------------------- the task through the trainer


def _trainer(held):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    overrides = {**SIZES, "mixers": list(KINDS), "remat": True, "experts_held": list(held)}
    cfg = TrainConfig(
        model_name="lfm2_24b_a2b", num_classes=VOCAB, compute_dtype="float32",
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
    assert state.batch_stats["select_bias"].shape == (4, EXPERTS)
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
        assert "loss_mtp" not in m and "attn_gate_mean" not in m
        assert m["loss"] == pytest.approx(m["loss_main"] + ALPHA * m["aux_loss"], rel=1e-5)
        assert m["sconv_out_rms_max"] > 0.0 and 0.3 < m["moe_held_share"] < 0.7
    change = [np.asarray(a) - b for a, b in zip(jax.tree.leaves(state.params), start)]
    scale = max(float(np.max(np.abs(c))) for c in want["change"])
    assert scale > 1e-4  # the weights moved
    for got, ref in zip(change, want["change"]):
        # As test_qwen3_next.py holds a leaf: by its norm and by the share of entries whose gradient is all
        # rounding (Adam moves such an entry by the rate with the rounding's sign).
        off = np.abs(got - ref)
        assert float(np.linalg.norm(off)) <= 8e-2 * float(np.linalg.norm(ref))
        assert int(np.sum(off > 3e-2 * scale)) <= 2e-2 * off.size
    # The selection bias is state: stepped by the sign rule on each step's counts, exactly.
    assert np.array_equal(np.asarray(state.batch_stats["select_bias"]), want["select_bias"])
    assert float(np.max(np.abs(want["select_bias"]))) > 0.0
