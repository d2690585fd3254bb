"""The Qwen3-Next family (gated delta-rule layers with a gated grouped-query
softmax layer every fourth, softmax-routed experts with a gated shared expert)
against its plain float32 reference, at toy sizes on the CPU: hidden 64, one
period of four layers, 2 key / 4 value delta-rule heads of 16, 4 query heads
on 2 key/value heads of 16 with 4 rotary lanes, 16 experts of which 4 a token,
vocabulary 97, 32 positions.

Tolerances. Program and reference both compute in float32 here, in different
orders (the rule in chunks against one token at a time, a sort and grouped
matmuls against a loop over experts), so ``TIGHT`` = 2e-5 of the compared
tensor's largest entry, as ``test_joyai.py``; the gradients of a four-layer
model are held to 1e-4 (the chunked rule's triangular system and the
recurrence round apart, and three such layers lie under the first one's
leaves). The seeds leave the margin between the 4th and the 5th routing
probability above 1e-6 at every token."""

import gc
import importlib
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
from benchmark.reference import qwen3_next as reference  # noqa: E402
from sav_tpu.models import create_model, model_task  # noqa: E402
from sav_tpu.models.joyai import LatentDecoderBlock, hybrid_mixers  # noqa: E402
from sav_tpu.models.layers import RMSNorm  # noqa: E402
from sav_tpu.models.layers.gated_attention import rotate_leading_lanes  # noqa: E402
from sav_tpu.models.layers.moe import _Router, gmm_tiling  # noqa: E402
from sav_tpu.ops import attention as attention_ops  # noqa: E402

flash = importlib.import_module("sav_tpu.ops.flash_attention")  # the package exports the function under that name

TIGHT = 2e-5
VOCAB, SEQ, BATCH, EXPERTS, TOP_K, ALPHA = 97, 32, 2, 16, 4, 1e-3
ATTENTION = {"num_heads": 4, "kv_heads": 2, "head_ch": 16, "rotary_ch": 4}
DELTA = {"key_heads": 2, "heads": 4, "key_ch": 16, "value_ch": 16, "conv_width": 4, "chunk": 8}
SIZES = dict(embed_dim=64, num_layers=4, expert_ch=32, num_experts=EXPERTS, top_k=TOP_K,
             loss_block_tokens=16, gated_attention=ATTENTION, gated_delta=DELTA)


def model_file(held=(0, EXPERTS), layers=4):
    """What ``benchmark/configs/qwen3_next_80b_a3b.json`` holds, at the toy sizes."""
    return {
        "hidden_size": 64, "num_layers": layers, "full_attention_interval": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
        "rope_theta": 10000000, "rms_norm_eps": 1e-6,
        "linear_num_key_heads": 2, "linear_key_head_dim": 16, "linear_num_value_heads": 4,
        "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_experts_published": EXPERTS, "expert_offset": held[0], "num_experts": held[1],
        "num_experts_per_tok": TOP_K, "vocab_size": VOCAB, "recipe": {"balance_alpha": ALPHA},
    }


def build(dtype=jnp.float32, **overrides):
    return create_model("qwen3_next_80b_a3b", num_classes=VOCAB, dtype=dtype, **{**SIZES, **overrides})


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


def bias_rows(layers=4):
    return {"select_bias": jnp.zeros((layers, EXPERTS))}


# -------------------------------------------------------- grouped key/value heads


def grouped_operands(length, heads=4, kv_heads=2, dim=256, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, length, heads, dim))
    k = jax.random.normal(ks[1], (2, length, kv_heads, dim))
    v = jax.random.normal(ks[2], (2, length, kv_heads, dim))
    return q, k, v, jax.random.normal(ks[3], (2, length, heads, dim))


def repeated_heads_einsum(q, k, v):
    """Causal attention with each key/value head written out for the query
    heads it serves: query head ``h`` reads head ``h // group``."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.stack([t[:, :, h // group] for h in range(q.shape[2])], axis=2) for t in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    visible = jnp.arange(q.shape[1])[:, None] >= jnp.arange(q.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(visible, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# (backend, length, flash blocks, the layout the rule picks)
GROUPED_CASES = [
    ("xla", 48, {}, None),
    ("pallas", 256, dict(block_q=128, block_kv=128, block_b=1), "in_place"),
    ("pallas", 200, {}, "head_major"),
]


@pytest.mark.parametrize("backend,length,blocks,layout", GROUPED_CASES,
                         ids=["xla", "flash_in_place", "flash_head_major"])
def test_grouped_heads_match_an_einsum_with_repeated_heads(backend, length, blocks, layout):
    """Values, dq, and dk / dv summed over the group, at head size 256 with 4
    query heads on 2 key/value heads. float32 throughout; the interpreted
    kernels sum in another order than the einsum: 2e-5 of the largest entry
    (dk and dv add eight, here two, heads' roundings: 5e-5)."""
    q, k, v, g = grouped_operands(length)
    if backend == "pallas":
        assert flash.layout_form(length, length, 256, 256, batch_heads=8, itemsize=4, **blocks) == layout

        def core(q, k, v):
            return flash.flash_attention(q, k, v, causal=True, interpret=True, **blocks)
    else:
        def core(q, k, v):
            return attention_ops.dot_product_attention(q, k, v, causal=True, backend="xla", logits_dtype=jnp.float32)

    out, pull = jax.vjp(jax.jit(core), q, k, v)
    want, want_pull = jax.vjp(repeated_heads_einsum, q, k, v)
    assert out.shape == q.shape and close(out, want)
    for name, got, ref in zip("dq dk dv".split(), pull(g), want_pull(g)):
        assert got.shape == ref.shape and close(got, ref, 5e-5), name


def test_the_dispatcher_reads_the_group_from_the_shapes_and_logs_it():
    q, k, v, _ = grouped_operands(256, dim=128)
    attention_ops.clear_dispatch_log()
    out = attention_ops.dot_product_attention(q, k, v, causal=True, backend="pallas")
    assert out.shape == q.shape
    (note,) = attention_ops.snapshot_dispatch_log()
    assert note["kv_heads"] == 2 and note["shape"] == [2, 256, 4, 128] and note["backend"] == "pallas"
    # float32 operands at the default blocks take block_b 8: head-major copies, which repeat the group.
    assert (note["layout"], note["grouped_kv"]) == ("head_major", "repeated")
    attention_ops.clear_dispatch_log()
    attention_ops.dot_product_attention(q, k[:, :, :1].repeat(4, axis=2), v[:, :, :1].repeat(4, axis=2), causal=True, backend="xla")
    (note,) = attention_ops.snapshot_dispatch_log()
    assert note["kv_heads"] == 4 and "grouped_kv" not in note
    with pytest.raises(ValueError, match="query heads"):
        flash.flash_attention(q, k[:, :, :1].repeat(3, axis=2), v[:, :, :1].repeat(3, axis=2), interpret=True)
    with pytest.raises(ValueError, match="no grouped heads"):
        attention_ops.dot_product_attention(q, k, v, backend="fused")


# ----------------------------------------------------------- the blocks' pieces


@pytest.mark.parametrize("lanes,dim", [(4, 16), (64, 256)])
def test_rotary_turns_a_quarter_of_the_lanes(lanes, dim):
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 3, dim))
    got = rotate_leading_lanes(x, lanes, 1e7)
    assert np.array_equal(np.asarray(got[..., lanes:]), np.asarray(x[..., lanes:]))  # the others pass
    assert np.allclose(np.asarray(got[:, 0]), np.asarray(x[:, 0]), atol=1e-7)  # position 0 turns by nothing
    for b in range(2):
        assert close(got[b], reference.rotate_leading_lanes(x[b], lanes, 1e7))
    # lane i is paired with lane i + lanes / 2: the pair's norm is kept, lane by lane
    half = lanes // 2
    pair = lambda t: jnp.square(t[..., :half]) + jnp.square(t[..., half:lanes])
    assert np.allclose(np.asarray(pair(got)), np.asarray(pair(x)), rtol=1e-4, atol=1e-6)


def test_the_norms_weight_is_an_offset_from_one():
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 8))
    module = RMSNorm(eps=1e-6, offset=True)
    fresh = module.init(jax.random.PRNGKey(0), x)["params"]
    assert list(fresh) == ["offset"] and float(jnp.max(jnp.abs(fresh["offset"]))) == 0.0
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (8,))
    got = module.apply({"params": {"offset": w}}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * (1.0 + w)
    assert close(got, want, 1e-6) and close(got, reference.norm(x, {"offset": w}, 1e-6), 1e-6)
    assert close(module.apply({"params": fresh}, x), RMSNorm(eps=1e-6).apply({"params": {"scale": jnp.ones(8)}}, x), 1e-7)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_softmax_router_against_a_written_out_top_k(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (24, 32))
    kernel = jax.random.normal(jax.random.PRNGKey(seed + 10), (32, EXPERTS)) * 32 ** -0.5
    router = _Router(EXPERTS, TOP_K, 1.0, "softmax")
    scores, chosen, weights_, _ = router.apply({"params": {"kernel": kernel}}, x, jnp.zeros((EXPERTS,)))
    logits = np.asarray(x, np.float64) @ np.asarray(kernel, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1)[:, :TOP_K]  # the k largest, largest first
    picked = np.take_along_axis(probs, order, axis=-1)
    assert np.array_equal(np.asarray(chosen), order)
    assert np.allclose(np.asarray(scores), probs, atol=1e-6) and np.allclose(np.asarray(scores).sum(-1), 1.0, atol=1e-6)
    assert np.allclose(np.asarray(weights_), picked / picked.sum(-1, keepdims=True), atol=1e-6)  # norm_topk_prob
    assert np.allclose(np.asarray(weights_).sum(-1), 1.0, atol=1e-6)
    ref_probs, ref_chosen, ref_weights = reference.route(x, {"route": {"kernel": kernel}}, model_file())
    assert np.array_equal(np.asarray(ref_chosen), order) and np.allclose(np.asarray(ref_weights), np.asarray(weights_), atol=1e-6)


def test_the_layer_pattern_and_the_registry():
    assert hybrid_mixers(4, 4) == ("gated_delta",) * 3 + ("gated_attention",)
    pattern = hybrid_mixers(48, 4)
    assert pattern.count("gated_delta") == 36 and pattern.count("gated_attention") == 12
    assert all(kind == "gated_attention" for kind in pattern[3::4])
    assert model_task("qwen3_next_80b_a3b") == "tokens_mtp"
    assert gmm_tiling(2048, 512) == (256, 1024, 512)  # the rule at this family's expert
    with pytest.raises(ValueError, match="multi-token"):
        build(mtp_modules=1).init({"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32), is_training=False)


# ------------------------------------------------------- model against reference


def test_the_tree_is_the_one_the_reference_reads(params):
    config = {**model_file(), "vocab_size": VOCAB}
    reference.check_layout(params, config)
    assert sorted(params) == ["embed", "final_norm", "layer_0", "layer_1", "layer_2", "layer_3", "lm_head"]
    assert sorted(params["layer_0"]) == ["GatedDeltaNetBlock_0", "attn_norm", "ffn_norm", "moe"]
    assert sorted(params["layer_3"]) == ["GatedSelfAttentionBlock_0", "attn_norm", "ffn_norm", "moe"]
    assert list(params["final_norm"]) == ["offset"] and "shared_gate" in params["layer_0"]["moe"]
    with pytest.raises(ValueError, match="not the configuration's"):
        reference.check_layout(params, {**config, "num_experts": 8})


def test_logits_match_the_reference(tokens, params):
    model = build()
    got = jax.jit(lambda p, t: model.apply({"params": p, "batch_stats": bias_rows()}, t, is_training=False))(
        params, tokens[:, :-1])["logits"]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.sequence_logits(params, row[:-1], model_file()) for row in tokens])
    assert got.shape == (BATCH, SEQ, VOCAB) and close(got, want)


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
    want_loss, want, counts = reference.make_loss_and_grad(model_file())(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= TIGHT * float(want_loss)
    assert "ce_mtp" not in out  # no module
    assert np.array_equal(np.asarray(jnp.sum(out["moe_counts"], axis=0)), np.asarray(counts))
    assert float(jnp.max(jnp.abs(state["batch_stats"]["select_bias"]))) == 0.0  # no selection bias, not stepped
    for name in ("gdn_decay_min", "gdn_state_rms_max", "attn_gate_mean"):
        assert out[name].shape == (BATCH,) and float(out[name][0]) == float(out[name][1])
    assert 0.0 < float(out["gdn_decay_min"][0]) < 1.0 and 0.0 < float(out["attn_gate_mean"][0]) < 1.0
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        assert close(got, ref, 1e-4), weights.path_of(path)


# ------------------------------------------------------- the share of a layer


@pytest.mark.parametrize("mixer", ["gated_delta", "gated_attention"])
def test_the_sixteen_shares_parts_add_up_to_the_uncut_layer(mixer):
    """16 toy experts over 16 shares of 1 (the deployment's 16 chips a layer):
    the routed parts the shares give, with the gated shared expert and the
    token mixer counted once, add up to the uncut reference's layer output.
    The mixer, the router and the shared expert are replicated: every share
    computes them alike."""
    shares, d, seq = 16, 64, 32
    sizes = dict(mlp_ch=32, num_experts=EXPERTS, top_k=TOP_K, routed_scale=1.0, norm_eps=1e-6, rope_theta=1e7,
                 mixer=mixer, mixer_sizes=DELTA if mixer == "gated_delta" else ATTENTION,
                 norm_offset=True, scoring="softmax", shared_gate=True)
    whole = LatentDecoderBlock(**sizes, experts_held=None)
    x = jax.random.normal(jax.random.PRNGKey(40), (1, seq, d))
    bias = jnp.zeros((EXPERTS,))
    abstract = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, x, bias))["params"]
    p = weights.draw_params(abstract, 5)
    model = model_file()
    index = 0 if mixer == "gated_delta" else 3  # the reference takes the kind from the layer's index
    with jax.default_matmul_precision("highest"):
        want, want_counts, _ = reference.layer(x[0], p, index, model)
        # What every share computes alike, once: the layer with NO routed expert's part.
        mix = reference.gated_delta_block if mixer == "gated_delta" else reference.gated_attention
        h = x[0] + mix(reference.norm(x[0], p["attn_norm"], 1e-6), p[reference.mixer_of(index, model)], model)
        y = reference.norm(h, p["ffn_norm"], 1e-6)
        shared = reference.shared_part(y, p["moe"])
    total = h + shared
    for share in range(shares):
        held = (share, 1)
        cut = dict(p["moe"], experts=jax.tree.map(lambda leaf: leaf[share:share + 1], p["moe"]["experts"]))
        out, counts, _, _ = LatentDecoderBlock(**sizes, experts_held=held).apply({"params": {**p, "moe": cut}}, x, bias)
        total = total + (out[0] - h - shared)  # this share's routed part alone
        assert float(jnp.sum(counts)) == seq * TOP_K  # each share routes over all 16
        assert np.array_equal(np.asarray(counts[0]), np.asarray(want_counts))
    assert close(total, want, 5e-5)


# ------------------------------------------------- the task through the trainer


def _trainer(held):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    overrides = {**SIZES, "remat": True, "experts_held": list(held)}
    cfg = TrainConfig(
        model_name="qwen3_next_80b_a3b", num_classes=VOCAB, compute_dtype="float32",
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
        # The first update runs at rate 0: two losses on the seeded weights, to float32's rounding. The third
        # follows an update in which Adam has normalised every entry, the ones whose gradient is all rounding
        # too (below): 2e-4 (6.6e-5 read; to all digits with another order of the same float32 sums).
        assert abs(m["loss"] - loss) <= (TIGHT if step < 2 else 2e-4) * loss
        assert "loss_mtp" not in m
        assert m["loss"] == pytest.approx(m["loss_main"] + ALPHA * m["aux_loss"], rel=1e-5)
        assert 0.0 < m["gdn_decay_min"] < 1.0 and m["gdn_state_rms_max"] > 0.0 and 0.0 < m["attn_gate_mean"] < 1.0
        assert 0.3 < m["moe_held_share"] < 0.7 and m["moe_bias_abs_max"] == 0.0
    change = [np.asarray(a) - b for a, b in zip(jax.tree.leaves(state.params), start)]
    scale = max(float(np.max(np.abs(c))) for c in want["change"])
    assert scale > 1e-4  # the weights moved
    for got, ref in zip(change, want["change"]):
        # Adam divides a leaf's first moment by the root of its second, so an entry moves by about the rate
        # whatever its gradient's size, and one whose gradient is smaller than the gradient's error (1e-5 of
        # the leaf's largest here, float32 sums in two orders: program and reference agree on every leaf's
        # gradient to that, test_loss_and_gradients) moves with the rounding's sign: the two sides then differ
        # by up to twice the rate in that entry. How many such entries a leaf has is a matter of how the
        # rounding fell: two runs of this test that differ only in the order of the rule's float32 sums read
        # 1.5e-2 and 3.6e-2 of the embedding's change (rows a batch holds once), 1e-3 and 1e-2 at worst
        # elsewhere, and 3e-4 and 5e-3 of a leaf's entries off by more than 3e-2 of the largest change. So a
        # leaf is held by its norm, 8e-2 of the change's own, and by the share of such entries, 2e-2: a leaf
        # left out, scaled or decayed wrongly reads 1. test_xing.py holds every entry to 3e-2 under one layer.
        off = np.abs(got - ref)
        assert float(np.linalg.norm(off)) <= 8e-2 * float(np.linalg.norm(ref))
        assert int(np.sum(off > 3e-2 * scale)) <= 2e-2 * off.size
    assert float(np.max(np.abs(np.asarray(state.batch_stats["select_bias"])))) == 0.0
