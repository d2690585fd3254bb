"""The Xing4.0 family (the expert decoder's sublayers between manifold-constrained
hyper-connected streams, YaRN on the rotary part) against its plain float32
reference, at toy sizes on the CPU: hidden 64, 4 streams, 3 layers (one dense),
2 heads of 24 / 16, 16 experts of which 4 a token, vocabulary 97, 32 positions,
with and without the multi-token-prediction module.

Tolerances. Program and reference both compute in float32 here, in different
orders (the streams as separate arrays against one ``[n, S, d]`` tensor, a sort
and grouped matmuls against a loop over experts), so ``TIGHT`` = 2e-5 of the
compared tensor's largest entry, as ``test_joyai.py``. The seeds leave the
margin between the 4th and the 5th routing score above 1e-5 at every token."""

import gc
import math
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
from benchmark.reference import joyai as joyai_reference, xing as reference  # noqa: E402
from sav_tpu.models import create_model, model_task  # noqa: E402
from sav_tpu.models.joyai import LatentDecoderBlock  # noqa: E402
from sav_tpu.models.layers import GatedFFBlock, LatentSelfAttentionBlock, RMSNorm, SparseMoEBlock  # noqa: E402
from sav_tpu.models.layers.hyper_connection import HyperConnection, sinkhorn_knopp  # noqa: E402
from sav_tpu.models.layers.moe import gmm_tiling  # noqa: E402
from sav_tpu.ops import rotary  # noqa: E402
from sav_tpu.train.tasks import mtp_lm_loss  # noqa: E402

TIGHT = 2e-5
VOCAB, SEQ, BATCH, EXPERTS, TOP_K, STREAMS = 97, 32, 2, 16, 4, 4
LAMBDA, ALPHA, GAMMA = 0.3, 1e-4, 1e-3
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
SIZES = dict(
    embed_dim=64, num_layers=3, first_dense=1, num_heads=2, q_rank=48, kv_rank=32, nope_ch=16, rope_ch=8,
    v_ch=16, mlp_ch=96, expert_ch=32, num_experts=EXPERTS, top_k=TOP_K, loss_block_tokens=16,
    rope_scaling=YARN,
)


def model_file(mtp, held=(0, EXPERTS), iters=20):
    """What ``benchmark/configs/xing4_29b_a4b.json`` holds, at the toy sizes."""
    return {
        "hidden_size": 64, "num_layers": 3, "num_attention_heads": 2, "q_lora_rank": 48,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32, "n_shared_experts": 1,
        "n_routed_experts_published": EXPERTS, "expert_offset": held[0], "n_routed_experts": held[1],
        "num_experts_per_tok": TOP_K, "routed_scaling_factor": 2, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": mtp, "rope_theta": 10000, "rope_scaling": YARN, "rms_norm_eps": 1e-6,
        "hc_mult": STREAMS, "hc_sinkhorn_iters": iters, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "vocab_size": VOCAB,
        "recipe": {"mtp_lambda": LAMBDA, "balance_alpha": ALPHA, "bias_update_rate": GAMMA},
    }


def build(mtp, dtype=jnp.float32, **overrides):
    return create_model("xing4_0_29b_a4b", num_classes=VOCAB, dtype=dtype, mtp_modules=mtp,
                        **{**SIZES, **overrides})


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
    """The seeded tree with the module; without it the tree is this less ``mtp``
    (``draw_params`` numbers the leaves, so the two are drawn apart)."""
    return {mtp: draw(build(mtp), tokens) for mtp in (0, 1)}


def close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) <= tol * float(np.max(np.abs(want)))


def bias_rows(mtp):
    return SIZES["num_layers"] - SIZES["first_dense"] + mtp


def program_terms(model, params, tokens, mtp):
    out, state = model.apply(
        {"params": params, "batch_stats": {"select_bias": jnp.zeros((bias_rows(mtp), EXPERTS))}},
        tokens[:, :-1], is_training=True, targets=tokens[:, 1:], mutable=["batch_stats", "losses"],
    )
    balance = sum(jnp.sum(x) for x in jax.tree.leaves(state["losses"]))
    return out, balance


def program_loss(model, params, tokens, mtp):
    out, balance = program_terms(model, params, tokens, mtp)
    return mtp_lm_loss(out["ce"], out.get("ce_mtp"), LAMBDA)[0] + ALPHA * balance


def reference_loss(params, tokens, model):
    bias = reference.initial_bias(model)
    with jax.default_matmul_precision("highest"):
        return sum(reference.sequence_loss(params, bias, row, model, len(tokens))[0] for row in tokens)


# ------------------------------------------------ program against reference


@pytest.mark.parametrize("mtp", [0, 1])
def test_registry_builds_the_family_and_the_layout_is_the_configurations(params, mtp):
    assert model_task("xing4_0_29b_a4b") == "tokens_mtp"
    reference.check_layout(params[mtp], model_file(mtp))
    with pytest.raises(ValueError, match="is not the configuration's"):
        reference.check_layout(params[mtp], model_file(mtp, held=(0, 4)))
    with pytest.raises(ValueError, match="is not the configuration's"):
        reference.check_layout(params[mtp], model_file(1 - mtp))
    assert ("mtp" in params[mtp]) == bool(mtp)
    hc = params[mtp]["layer_1"]["hc_ffn"]
    assert {k: v.shape for k, v in hc.items()} == {"kernel": (4 * 64, 24), "scale": (3,), "bias": (24,)}


def test_the_registry_entry_is_the_public_configs_sizes():
    from sav_tpu.models.registry import _REGISTRY

    _, sizes = _REGISTRY["xing4_0_29b_a4b"]
    assert sizes == {
        "embed_dim": 3584, "num_layers": 40, "num_heads": 32, "q_rank": 768, "kv_rank": 512, "nope_ch": 128,
        "rope_ch": 64, "v_ch": 128, "mlp_ch": 9216, "expert_ch": 1024, "num_experts": 64, "top_k": 4,
        "routed_scale": 2.0, "first_dense": 2, "mtp_modules": 1, "rope_theta": 1e4, "norm_eps": 1e-6,
        "rope_scaling": {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
                         "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "hc_res_clamp": (-30.0, 30.0),
        "kept_under_remat": ("flash_out", "flash_lse", "attn_out", "mla_latent", "ffn_gate", "ffn_up",
                             "moe_route", "moe_order", "hc_maps"),
    }


def test_the_seeded_routing_has_a_margin(params, tokens):
    model = model_file(1)
    p = params[1]
    streams = reference.fan_out(p["embed"]["embedding"][tokens[0, :-1]], model)
    with jax.default_matmul_precision("highest"):
        streams, _, _ = reference.layer(streams, p["layer_0"], None, model)
        streams, _ = reference.connected(
            streams, p["layer_1"]["hc_attn"],
            lambda u: (reference.latent_attention(
                reference.rms_norm(u, p["layer_1"]["attn_norm"], 1e-6), p["layer_1"]["LatentSelfAttentionBlock_0"], model
            ), None),
            model,
        )
        h_pre, _, _ = reference.connection_maps(streams, p["layer_1"]["hc_ffn"], model)
        x = reference.rms_norm(jnp.einsum("si,isd->sd", h_pre, streams), p["layer_1"]["ffn_norm"], 1e-6)
        scores, _, _ = joyai_reference.route(x, p["layer_1"]["moe"], 0.0, model)
    ranked = jnp.sort(scores, axis=-1)[:, ::-1]
    assert float(jnp.min(ranked[:, TOP_K - 1] - ranked[:, TOP_K])) > 1e-5


def test_main_logits_match_the_reference(params, tokens):
    p, model = params[0], model_file(0)
    out = build(0).apply(
        {"params": p, "batch_stats": {"select_bias": jnp.zeros((2, EXPERTS))}}, tokens[:, :-1], is_training=False
    )
    assert out["logits"].shape == (BATCH, SEQ, VOCAB)
    with jax.default_matmul_precision("highest"):
        for b in range(BATCH):
            streams = reference.fan_out(p["embed"]["embedding"][tokens[b, :-1]], model)
            for i in range(3):
                streams, _, _ = reference.layer(streams, p[f"layer_{i}"], jnp.zeros((EXPERTS,)) if i else None, model)
            want = reference.rms_norm(jnp.sum(streams, axis=0), p["final_norm"], 1e-6) @ p["lm_head"]["kernel"]
            assert close(out["logits"][b], want)


@pytest.fixture(scope="module")
def reference_loss_and_grad(params, tokens):
    return {
        mtp: jax.jit(jax.value_and_grad(lambda p, mtp=mtp: reference_loss(p, tokens, model_file(mtp))))(params[mtp])
        for mtp in (0, 1)
    }


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("mtp", [0, 1])
def test_loss_and_gradient_match_the_reference(params, tokens, reference_loss_and_grad, mtp, remat):
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(build(mtp, remat=remat), p, tokens, mtp)))(params[mtp])
    want_loss, want = reference_loss_and_grad[mtp]
    assert abs(float(loss) - float(want_loss)) <= TIGHT * float(want_loss)
    flat, want_flat = jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)
    scale = max(float(jnp.max(jnp.abs(w))) for w in want_flat)
    for (path, got), w in zip(flat, want_flat):
        assert float(jnp.max(jnp.abs(got - w))) <= TIGHT * scale, jax.tree_util.keystr(path)
    # Every leaf takes a gradient, the maps' three kinds of leaves too.
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in want_flat)


@pytest.mark.parametrize("mtp", [0, 1])
def test_the_terms_the_counts_and_the_stream_metrics(params, tokens, mtp):
    out, _ = program_terms(build(mtp), params[mtp], tokens, mtp)
    model = model_file(mtp)
    assert ("ce_mtp" in out) == bool(mtp)
    with jax.default_matmul_precision("highest"):
        for b in range(BATCH):
            ce, ce_mtp, _, counts = reference.sequence_terms(params[mtp], reference.initial_bias(model), tokens[b], model)
            assert close(out["ce"][b], ce)
            assert ce_mtp is None if not mtp else close(out["ce_mtp"][b, :-1], ce_mtp)
            assert np.array_equal(np.asarray(out["moe_counts"][b]), np.asarray(counts))
    assert out["moe_counts"].shape == (BATCH, bias_rows(mtp), EXPERTS)
    # Rows are normalised last (1 / (1 + hc_eps)); the columns are where twenty iterations got to.
    assert out["hc_doubly_stochastic_err"].shape == (BATCH,) and 0 < float(out["hc_doubly_stochastic_err"][0]) < 1e-2
    # The first sublayer mixes n copies of one row: its gain is 1; no doubly stochastic mix passes it.
    assert float(out["hc_stream_gain"][0]) == pytest.approx(1.0, abs=1e-5)


@pytest.mark.parametrize("iters", [1, 3])
def test_fewer_sinkhorn_iterations_are_another_model(params, tokens, reference_loss_and_grad, iters):
    """What the comparison is for: a step that stops the projection early is
    outside the tolerances the full one meets (the loss by 60 and 4.6 times
    ``TIGHT``, the maps' gradient by 0.19 and 0.03 of its largest entry)."""
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: program_loss(build(0, hc_sinkhorn_iters=iters), p, tokens, 0)
    ))(params[0])
    want_loss, want = reference_loss_and_grad[0]
    leaf, want_leaf = grads["layer_1"]["hc_ffn"]["kernel"], want["layer_1"]["hc_ffn"]["kernel"]
    assert abs(float(loss) - float(want_loss)) > (10 if iters == 1 else 2) * TIGHT * float(want_loss)
    assert not close(leaf, want_leaf, 100 * TIGHT)


def test_bfloat16_fails_the_float32_tolerances(params, tokens, reference_loss_and_grad):
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(build(0, jnp.bfloat16), p, tokens, 0)))(params[0])
    want_loss, want = reference_loss_and_grad[0]
    assert abs(float(loss) - float(want_loss)) > 5 * TIGHT * float(want_loss)
    assert not close(grads["lm_head"]["kernel"], want["lm_head"]["kernel"], 100 * TIGHT)
    assert not close(grads["layer_1"]["hc_ffn"]["kernel"], want["layer_1"]["hc_ffn"]["kernel"], 100 * TIGHT)


# ------------------------------------------------------- the share of a layer


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """8 toy experts over 4 shares of 2: the routed parts the shares give, with
    the shared expert and the residual mix (``H_res X`` and ``h_post``) counted
    once, add up to the uncut reference's layer output. The maps, the router
    and the shared expert are replicated: every share computes them alike."""
    experts, shares, d, seq = 8, 4, 64, 32
    sizes = dict(num_heads=2, q_rank=48, kv_rank=32, nope_ch=16, rope_ch=8, v_ch=16, mlp_ch=32,
                 num_experts=experts, top_k=TOP_K, routed_scale=2.0, rope_theta=1e4, norm_eps=1e-6,
                 rope_scaling=YARN, hc={"streams": STREAMS})
    whole = LatentDecoderBlock(**sizes, experts_held=None)
    x = tuple(jax.random.normal(jax.random.PRNGKey(40 + i), (1, seq, d)) for i in range(STREAMS))
    bias = jnp.zeros((experts,))
    abstract = jax.eval_shape(lambda: whole.init({"params": jax.random.PRNGKey(0)}, x, bias))["params"]
    p = weights.draw_params(abstract, 5)
    model = {**model_file(0, held=(0, experts)), "n_routed_experts_published": experts}
    with jax.default_matmul_precision("highest"):
        want, _, _ = reference.layer(jnp.stack([s[0] for s in x]), p, bias, model)  # [n, S, d]

        # What every share computes alike, once: the layer with NO routed expert's part.
        moe = p["moe"]
        streams = jnp.stack([s[0] for s in x])
        streams, _ = reference.connected(
            streams, p["hc_attn"],
            lambda u: (reference.latent_attention(reference.rms_norm(u, p["attn_norm"], 1e-6), p["LatentSelfAttentionBlock_0"], model), None),
            model,
        )
        _, h_post, h_res = reference.connection_maps(streams, p["hc_ffn"], model)
        once = jnp.einsum("sij,jsd->isd", h_res, streams)

    total = once
    for share in range(shares):
        held = (share * experts // shares, experts // shares)
        cut = dict(moe, experts=jax.tree.map(lambda leaf: leaf[held[0]:held[0] + held[1]], moe["experts"]))
        out, counts, _, _ = LatentDecoderBlock(**sizes, experts_held=held).apply({"params": {**p, "moe": cut}}, x, bias)
        # A share's layer output is once + h_post (routed part of its experts + shared expert).
        part = jnp.stack([s[0] for s in out]) - once
        if share:  # the shared expert rides in every share's result: count it once
            y = reference.rms_norm(jnp.einsum("si,isd->sd", *_pre(streams, p, model)), p["ffn_norm"], 1e-6)
            part = part - h_post.T[:, :, None] * joyai_reference.mlp(y, moe["shared"])[None]
        total = total + part
        assert float(jnp.sum(counts)) == seq * TOP_K  # each share routes over all 8
    assert close(total, want, 5e-5)


def _pre(streams, p, model):
    h_pre, _, _ = reference.connection_maps(streams, p["hc_ffn"], model)
    return h_pre, streams


# ------------------------------------------------------ the residual path alone


def test_one_stream_is_the_plain_residual_to_the_last_bit():
    """``hc_mult`` 1: no leaf of the path exists and the layer is ``x +
    F(RMSNorm(x))`` twice, bit for bit what the modules give when called by hand."""
    sizes = dict(num_heads=2, q_rank=48, kv_rank=32, nope_ch=16, rope_ch=8, v_ch=16, rope_theta=1e4, norm_eps=1e-6)
    block = LatentDecoderBlock(**sizes, mlp_ch=32, num_experts=EXPERTS, top_k=TOP_K, routed_scale=2.0, experts_held=(4, 8))
    x, bias = jax.random.normal(jax.random.PRNGKey(1), (BATCH, SEQ, 64)), jnp.zeros((EXPERTS,))
    p = weights.draw_params(jax.eval_shape(lambda: block.init({"params": jax.random.PRNGKey(0)}, x, bias))["params"], 3)
    assert sorted(p) == ["LatentSelfAttentionBlock_0", "attn_norm", "ffn_norm", "moe"]
    out, counts, balance, stats = block.apply({"params": p}, x, bias)
    assert stats is None

    def norm(name, v):
        return RMSNorm(eps=1e-6).apply({"params": p[name]}, v)

    a = LatentSelfAttentionBlock(**sizes).apply({"params": p["LatentSelfAttentionBlock_0"]}, norm("attn_norm", x))
    h = x + a
    m, want_counts, _, _ = SparseMoEBlock(num_experts=EXPERTS, top_k=TOP_K, hidden_ch=32, routed_scale=2.0,
                                       experts_held=(4, 8)).apply({"params": p["moe"]}, norm("ffn_norm", h), bias)
    assert np.array_equal(np.asarray(out), np.asarray(h + m)) and np.array_equal(np.asarray(counts), np.asarray(want_counts))
    # And with the dense FFN.
    dense = LatentDecoderBlock(**sizes, mlp_ch=96, num_experts=0, top_k=TOP_K, routed_scale=2.0, experts_held=None)
    pd = weights.draw_params(jax.eval_shape(lambda: dense.init({"params": jax.random.PRNGKey(0)}, x, None))["params"], 4)
    out, _, _, _ = dense.apply({"params": pd}, x, None)
    a = LatentSelfAttentionBlock(**sizes).apply(
        {"params": pd["LatentSelfAttentionBlock_0"]}, RMSNorm(eps=1e-6).apply({"params": pd["attn_norm"]}, x))
    h = x + a
    f = GatedFFBlock(hidden_ch=96).apply({"params": pd["GatedFFBlock_0"]}, RMSNorm(eps=1e-6).apply({"params": pd["ffn_norm"]}, h))
    assert np.array_equal(np.asarray(out), np.asarray(h + f))
    u, merge = HyperConnection(streams=1).apply({}, x)
    assert u is x and np.array_equal(np.asarray(merge(a)[0]), np.asarray(x + a))


def _extremes(case):
    n = STREAMS
    if case == "unit_normal":
        return jax.random.normal(jax.random.PRNGKey(0), (4096, n, n))
    if case == "all_at_the_upper_clamp":
        return jnp.full((1, n, n), 30.0)
    if case == "all_at_the_lower_clamp":
        return jnp.full((1, n, n), -30.0)
    if case == "a_permutation_at_the_clamps":
        return jnp.where(jnp.roll(jnp.eye(n, dtype=bool), 1, axis=1), 30.0, -30.0)[None]
    if case == "one_row_at_the_upper_clamp":
        return jnp.zeros((1, n, n)).at[:, 0, :].set(30.0)
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "unit_normal", "all_at_the_upper_clamp", "all_at_the_lower_clamp", "a_permutation_at_the_clamps",
    "one_row_at_the_upper_clamp",
])
def test_sinkhorn_rows_and_columns_sum_to_one_after_twenty_iterations(case):
    logits = jnp.clip(_extremes(case), -30.0, 30.0)
    h = sinkhorn_knopp(logits, 20, 1e-6)
    assert np.all(np.isfinite(np.asarray(h))) and float(jnp.min(h)) >= 0.0
    # The rows are normalised last: 1 / (1 + hc_eps). The columns are where
    # twenty iterations got to: exact from the clamps' corners, 4e-4 at worst
    # over 4,096 matrices of unit-normal logits (what the seeded weights give).
    columns = 1e-3 if case == "unit_normal" else 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(h, axis=-1) - 1.0))) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(h, axis=-2) - 1.0))) < columns
    # The reference's own twenty iterations are the same numbers.
    assert close(h, reference.sinkhorn_knopp(logits, 20, 1e-6), 1e-6)


def test_the_registrys_start_is_the_plain_residual_over_equal_streams():
    """The initialiser (zero ``Phi``, small gates, the static start): ``u`` is
    the streams' mean, the result is added to every stream, the streams stay
    apart: over ``n`` copies it is ``x + F(x)`` in every stream."""
    hc = HyperConnection(streams=STREAMS)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 64))
    v = hc.init({"params": jax.random.PRNGKey(0)}, (x,) * STREAMS)
    assert float(jnp.max(jnp.abs(v["params"]["kernel"]))) == 0.0 and np.allclose(v["params"]["scale"], 0.01)
    u, merge = hc.apply(v, (x,) * STREAMS)
    assert close(u, x, 1e-5)
    merged, (err, gain) = merge(2.0 * x)
    for stream in merged:
        assert close(stream, 3.0 * x, 1e-3)
    assert float(err) < 1e-3 and float(gain) == pytest.approx(1.0, abs=1e-3)


# ------------------------------------------------------------ YaRN and tiles


def test_yarn_frequencies_against_a_hand_count():
    scaling = {**YARN, "original_max_position_embeddings": 4096}
    # The pair that turns r times over 4,096 positions: 64 ln(4096 / (2 pi r)) / (2 ln 1e4).
    fast = 64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(1e4))
    slow = 64 * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(1e4))
    assert (math.floor(fast), math.ceil(slow)) == (10, 23)
    got = np.asarray(rotary.yarn_inv_freq(64, 1e4, scaling), np.float64)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(got[:11], plain[:11], rtol=1e-6)  # 32 turns or more: kept
    assert np.allclose(got[23:], plain[23:] / 64, rtol=1e-6)  # one turn or fewer: over the factor
    for i in (11, 16, 22):  # blended linearly in the pair's index
        ramp = (i - 10) / 13
        assert got[i] == pytest.approx(plain[i] * (1 - ramp) + plain[i] / 64 * ramp, rel=1e-6)
    assert np.allclose(got, np.asarray(reference.yarn_frequencies(64, 1e4, scaling)), rtol=1e-6)


@pytest.mark.parametrize("what", ["softmax_scale", "amplitude", "no_scaling"])
def test_yarn_scales_against_a_hand_count(what):
    scaling = {**YARN, "original_max_position_embeddings": 4096}
    if what == "softmax_scale":
        assert rotary.yarn_softmax_scale(scaling) == pytest.approx((0.1 * math.log(64) + 1) ** 2)
        assert rotary.yarn_softmax_scale(scaling) == pytest.approx(2.005, abs=5e-4)
    elif what == "amplitude":
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 8))
        same = rotary.apply_rotary_interleaved(x, 1e4, scaling)
        louder = rotary.apply_rotary_interleaved(x, 1e4, {**scaling, "mscale": 2})
        ratio = rotary.yarn_mscale(64, 2) / rotary.yarn_mscale(64, 1)
        assert np.allclose(np.asarray(louder), ratio * np.asarray(same), rtol=1e-5, atol=1e-5)
        # a rotation keeps each pair's length
        assert np.allclose(np.sum(np.square(np.asarray(same)), -1), np.sum(np.square(np.asarray(x)), -1), rtol=1e-4)
    else:
        assert rotary.yarn_softmax_scale(None) == 1.0 and rotary.yarn_mscale(1, 1) == 1.0
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8))
        assert np.array_equal(np.asarray(rotary.apply_rotary_interleaved(x, 32e6)),
                              np.asarray(rotary.apply_rotary_interleaved(x, 32e6, None)))
        with pytest.raises(ValueError, match="yarn alone"):
            rotary.yarn_inv_freq(8, 1e4, {**scaling, "type": "linear"})


@pytest.mark.parametrize("widths,tiling", [
    ((2048, 768), (256, 1024, 768)),  # JoyAI-LLM-Flash: the constant the rule replaced
    ((3584, 1024), (256, 896, 1024)),  # Xing4.0: 3,584 = 4 x 896
    ((64, 32), (256, 64, 32)),  # a toy no multiple of 128 divides: the width itself
])
def test_the_grouped_matmuls_tile_follows_the_widths(widths, tiling):
    assert gmm_tiling(*widths) == tiling


# ------------------------------------------------- the task through the trainer


FIT_LAYERS = 2  # one dense, one routed: the trainer's and the reference's compiles are what this test costs


def _trainer(mtp, held=(4, 8)):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    overrides = {**SIZES, "num_layers": FIT_LAYERS, "remat": True, "experts_held": list(held), "mtp_modules": mtp}
    cfg = TrainConfig(
        model_name="xing4_0_29b_a4b", num_classes=VOCAB, compute_dtype="float32",
        global_batch_size=BATCH, model_overrides=overrides,
        label_smoothing=0.0, warmup_epochs=0, base_lr=3e-4, lr_scaling_divisor=BATCH,
        weight_decay=0.1, aux_loss_weight=ALPHA, log_every_steps=1, fleet=False, transpose_images=False,
    )
    return Trainer(cfg, mesh=create_mesh({"data": 1}, devices=jax.devices()[:1]))


@pytest.fixture(scope="module")
def batches():
    return [
        jax.random.randint(jax.random.PRNGKey(20 + i), (BATCH, SEQ + 1), 0, VOCAB, jnp.int32)
        for i in range(3)
    ]


@pytest.mark.parametrize("mtp", [0, 1])
def test_fit_trains_the_family_and_three_updates_match_the_reference(tokens, batches, mtp):
    held = (4, 8)
    held_params = draw(build(mtp, experts_held=held, num_layers=FIT_LAYERS), tokens)
    trainer = _trainer(mtp, held)
    state = trainer.init_state(0).replace(params=jax.tree.map(jnp.array, held_params))
    assert state.batch_stats["select_bias"].shape == (1 + mtp, EXPERTS)
    state, history = trainer.fit(iter({"tokens": np.asarray(b)} for b in batches), num_steps=3, state=state)
    logged = [h for h in history if "loss" in h]
    hp = {k: getattr(trainer.config, k) for k in (
        "base_lr", "global_batch_size", "lr_scaling_divisor", "num_train_images", "warmup_epochs",
        "num_epochs", "end_lr", "weight_decay", "clip_grad_norm",
    )}
    start = [np.asarray(leaf) for leaf in jax.tree.leaves(held_params)]
    want = reference.follow_steps(
        jax.tree.map(jnp.array, held_params), batches, hp, {**model_file(mtp, held), "num_layers": FIT_LAYERS}
    )
    assert len(logged) == 3 and int(state.step) == 3
    for m, loss in zip(logged, want["losses"]):
        assert abs(m["loss"] - loss) <= TIGHT * loss
        assert ("loss_mtp" in m) == bool(mtp)
        extra = LAMBDA * m["loss_mtp"] if mtp else 0.0
        assert m["loss"] == pytest.approx(m["loss_main"] + extra + ALPHA * m["aux_loss"], rel=1e-5)
        assert 0 < m["hc_doubly_stochastic_err"] < 1e-2 and m["hc_stream_gain"] == pytest.approx(1.0, abs=1e-4)
        assert 0.3 < m["moe_held_share"] < 0.7
    change = [np.asarray(a) - b for a, b in zip(jax.tree.leaves(state.params), start)]
    scale = max(float(np.max(np.abs(c))) for c in want["change"])
    assert scale > 1e-4  # the weights moved
    for got, ref in zip(change, want["change"]):
        # Adam divides a leaf's first moment by the root of its second: an entry whose gradient is
        # all rounding moves by the rate whatever its size (a gate of the module's layer, 3e-3 to 7e-3 of
        # the scale from run to run; every other leaf under 2e-3).
        assert float(np.max(np.abs(got - ref))) <= 1e-2 * scale
    assert np.array_equal(np.asarray(state.batch_stats["select_bias"]), want["select_bias"])


def test_the_paths_leaves_match_no_sharding_rule_and_replicate(params):
    from jax.sharding import PartitionSpec as P

    from sav_tpu.parallel.sharding import DEFAULT_EP_RULES, DEFAULT_TP_RULES, param_path_specs

    for rules in (DEFAULT_TP_RULES, DEFAULT_EP_RULES):
        specs = param_path_specs(params[1], rules)
        for layer in ("layer_0", "layer_1", "layer_2"):
            for sublayer in ("hc_attn", "hc_ffn"):
                assert all(spec == P() for spec in jax.tree.leaves(
                    specs[layer][sublayer], is_leaf=lambda s: isinstance(s, P)))
        assert all(spec == P() for spec in jax.tree.leaves(
            specs["mtp"]["layer"]["hc_ffn"], is_leaf=lambda s: isinstance(s, P)))
    assert param_path_specs(params[1], DEFAULT_EP_RULES)["layer_1"]["moe"]["experts"]["fc2"]["experts_w2"][0] == "expert"
