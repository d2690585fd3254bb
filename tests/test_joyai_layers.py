"""The layers the JoyAI-LLM-Flash family brought, against its plain float32
reference and against each other, at toy sizes on the CPU: the latent
attention block, rotary on adjacent pairs of a slice of the head, the flash
kernel with a value head narrower than the query's (interpret mode), the
dropless sorted expert path against a loop (uneven routing, an expert with
no token, every token on the same experts), and one chip's share of an
expert-parallel layer (sixteen shares and the shared expert once are the uncut
layer). ``test_joyai.py`` holds the model, the task and the trainer; the toy
sizes and the tolerance are its."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_joyai import (  # noqa: F401  (fixtures are used by name)
    BATCH, EXPERTS, LAMBDA, SEQ, TIGHT, TOP_K, build, close, model_file, params, program_loss, tokens,
)

from benchmark import weights
from benchmark.reference import joyai as reference
from sav_tpu.models.layers import LatentSelfAttentionBlock, SparseMoEBlock
from sav_tpu.ops.attention import xla_attention
from sav_tpu.ops.flash_attention import flash_attention
from sav_tpu.ops.rotary import apply_rotary_interleaved
from sav_tpu.train.tasks import mtp_lm_loss

@pytest.fixture(scope="module", autouse=True)
def _leave_no_live_buffers():
    """The jitted closures here hold their constants in jax's caches; tests
    that rank the process's live buffers (``test_memdump.py``) may share this
    worker."""
    yield
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------ latent attention


def test_latent_attention_block_matches_the_reference(params):
    x = jax.random.normal(jax.random.PRNGKey(5), (BATCH, SEQ, 64))
    block = LatentSelfAttentionBlock(
        num_heads=2, q_rank=48, kv_rank=32, nope_ch=16, rope_ch=8, v_ch=16, rope_theta=32e6, backend="xla",
    )
    p = params["layer_1"]["LatentSelfAttentionBlock_0"]
    got = block.apply({"params": p}, x)
    with jax.default_matmul_precision("highest"):
        for b in range(BATCH):
            assert close(got[b], reference.latent_attention(x[b], p, model_file()))
    pallas = LatentSelfAttentionBlock(
        num_heads=2, q_rank=48, kv_rank=32, nope_ch=16, rope_ch=8, v_ch=16, rope_theta=32e6, backend="pallas",
    ).apply({"params": p}, x)
    assert close(pallas, got, 1e-5)


def test_interleaved_rotary_rotates_adjacent_pairs_of_the_slice_it_is_given():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3, 8))
    got = apply_rotary_interleaved(x, 32e6)
    for pos in range(6):
        for i in range(4):
            angle = pos * 32e6 ** (-2 * i / 8)
            a, b = x[0, pos, :, 2 * i], x[0, pos, :, 2 * i + 1]
            np.testing.assert_allclose(got[0, pos, :, 2 * i], a * np.cos(angle) - b * np.sin(angle), atol=1e-6)
            np.testing.assert_allclose(got[0, pos, :, 2 * i + 1], b * np.cos(angle) + a * np.sin(angle), atol=1e-6)
    # A key with no head axis is rotated as each head would be.
    assert np.allclose(apply_rotary_interleaved(x[:, :, 0], 32e6), got[:, :, 0])
    # float32 angles under a bfloat16 input: at position 4,000 a bfloat16
    # cosine would be off in the second digit.
    long = jnp.ones((1, 4001, 2), jnp.bfloat16)
    want = np.cos(4000.0) - np.sin(4000.0)
    assert abs(float(apply_rotary_interleaved(long, 32e6)[0, 4000, 0]) - want) < 1e-2


def _qkv(seq, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (1, seq, 2)
    return tuple(jax.random.normal(k, shape + (d,)) for k, d in zip(ks, (192, 192, 128, 128)))


@pytest.mark.parametrize("seq,blocks", [(256, (128, 128)), (200, (64, 128))])
def test_flash_with_a_narrower_value_head_forward_and_gradients(seq, blocks):
    """192 / 128 causal in interpret mode against the dense path."""
    q, k, v, g = _qkv(seq)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=blocks[0], block_kv=blocks[1])
    dense = lambda q, k, v: xla_attention(q, k, v, causal=True, logits_dtype=jnp.float32)
    out = flash(q, k, v)
    assert out.shape == (1, seq, 2, 128) and close(out, dense(q, k, v), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * g), (0, 1, 2))(q, k, v)
    for a, b, dim in zip(got, want, (192, 192, 128)):
        assert a.shape[-1] == dim and close(a, b, 1e-5)


def test_flash_pads_no_head_of_the_latent_core_in_hbm():
    """Neither the 192-wide query and key nor the 128-wide value and output
    is widened to the other's size or to more lanes; the zoo's 64-wide head
    is still padded to one lane tile."""
    from sav_tpu.ops.flash_attention import _pad_head

    assert [_pad_head(d) for d in (48, 64, 128, 192, 256)] == [128, 128, 128, 192, 256]
    q, k, v, _ = _qkv(128)
    text = str(jax.make_jaxpr(lambda *a: flash_attention(*a, causal=True, block_q=128, block_kv=128))(q, k, v))
    assert "f32[2,128,256]" not in text and "f32[2,128,192]" in text and "f32[2,128,128]" in text


# ------------------------------------------------------------ expert layer


def _moe(held=None, experts=EXPERTS, k=TOP_K, **kw):
    return SparseMoEBlock(num_experts=experts, top_k=k, hidden_ch=32, routed_scale=2.5, experts_held=held, **kw)


def _moe_reference(x, p, bias, held=(0, EXPERTS), experts=EXPERTS, k=TOP_K):
    model = {**model_file(held), "n_routed_experts_published": experts, "num_experts_per_tok": k}
    with jax.default_matmul_precision("highest"):
        return [reference.expert_layer(row, p, bias, model) for row in x]


def _routed_at_fan_in_scale(p):
    """The benchmark draws a stacked expert leaf at 0.02, a sixth of this
    width's ``fan_in ** -0.5``: scaled up, the routed branch is as large as
    the shared expert and the comparisons below are of it."""
    return {**p, "experts": jax.tree.map(lambda a: 6.0 * a, p["experts"])}


@pytest.fixture(scope="module")
def moe_params(params):
    return _routed_at_fan_in_scale(params["layer_1"]["moe"])


ROUTINGS = {
    # A large bias on few experts sends every token there; an expert whose
    # bias is -10 sees no token.
    "seeded": np.zeros(EXPERTS),
    "uneven": np.where(np.arange(EXPERTS) < 6, 0.5, 0.0),
    "an_expert_with_no_token": np.where(np.arange(EXPERTS) == 3, -10.0, 0.0),
    "all_tokens_on_the_same_experts": np.where(np.arange(EXPERTS) < TOP_K, 10.0, 0.0),
}


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_sorted_path_matches_the_loop_over_experts(moe_params, case):
    bias = jnp.asarray(ROUTINGS[case], jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, 64))

    def run(p, x):
        return _moe().apply({"params": p}, x, bias)

    (y, counts, balance), want = run(moe_params, x), _moe_reference(x, moe_params, bias)
    for b in range(BATCH):
        assert close(y[b], want[b][0]) and np.array_equal(np.asarray(counts[b]), np.asarray(want[b][1]))
    assert float(balance) == pytest.approx(float(np.mean([w[2] for w in want])), rel=1e-5)
    if case == "an_expert_with_no_token":
        assert float(jnp.sum(counts[:, 3])) == 0
    if case == "all_tokens_on_the_same_experts":
        assert float(jnp.sum(counts[:, :TOP_K])) == BATCH * SEQ * TOP_K
    g = jax.random.normal(jax.random.PRNGKey(8), y.shape)
    got = jax.grad(lambda p, x: jnp.sum(run(p, x)[0] * g) + run(p, x)[2], (0, 1))(moe_params, x)

    def loop(p, x):
        out = _moe_reference(x, p, bias)
        return sum(jnp.sum(o[0] * g[b]) for b, o in enumerate(out)) + sum(o[2] for o in out) / BATCH

    want = jax.grad(loop, (0, 1))(moe_params, x)
    scale = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= TIGHT * scale


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test: 256 experts over 16 chips, 8 a token. Every
    chip computes the shared expert alike; the routed parts of all the
    shares add up to the uncut layer's routed part."""
    experts, k, chips = 256, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 64, 64))
    whole = _moe(experts=experts, k=k)
    abstract = jax.eval_shape(lambda: whole.init(jax.random.PRNGKey(0), x, jnp.zeros((experts,))))["params"]
    p = _routed_at_fan_in_scale(weights.draw_params(abstract, 5))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(10), (experts,))
    uncut, counts, balance = whole.apply({"params": p}, x, bias)
    from sav_tpu.models.layers import GatedFFBlock

    shared_out = GatedFFBlock(hidden_ch=32).apply({"params": p["shared"]}, x.reshape(64, 64)).reshape(x.shape)
    routed = jnp.zeros_like(uncut)
    for chip in range(chips):
        held = (chip * experts // chips, experts // chips)
        cut = jax.tree.map(lambda a: a, p)
        for group, leaf in (("fc1", "gate_experts_w1"), ("fc1", "up_experts_w1"), ("fc2", "experts_w2")):
            cut["experts"][group][leaf] = p["experts"][group][leaf][held[0]:held[0] + held[1]]
        part, part_counts, part_balance = _moe(held, experts=experts, k=k).apply({"params": cut}, x, bias)
        # The router, the counts and the balance loss stay 256 wide on every chip.
        assert np.array_equal(np.asarray(part_counts), np.asarray(counts))
        assert float(part_balance) == pytest.approx(float(balance), rel=1e-6)
        routed = routed + (part - shared_out)
    assert close(routed + shared_out, uncut) and close(routed, uncut - shared_out, 1e-4)
    assert float(jnp.sum(counts)) == 64 * k
    assert float(jnp.linalg.norm(routed)) > 0.3 * float(jnp.linalg.norm(shared_out))


def test_holding_every_expert_is_the_uncut_layer(moe_params):
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, 64))
    bias = jnp.zeros((EXPERTS,))
    y, counts, _ = _moe().apply({"params": moe_params}, x, bias)
    y_all, counts_all, _ = _moe((0, EXPERTS)).apply({"params": moe_params}, x, bias)
    assert np.array_equal(np.asarray(y), np.asarray(y_all)) and np.array_equal(np.asarray(counts), np.asarray(counts_all))
    with pytest.raises(ValueError, match="do not fit"):
        _moe((8, 12)).init(jax.random.PRNGKey(0), x, bias)


def test_the_expert_path_builds_nothing_of_tokens_by_experts_by_more(moe_params):
    """No dispatch tensor: beyond the [T, E] scores nothing has both a token
    axis and an expert axis, and the largest value is [T k, D]."""
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, 64))
    jaxpr = jax.make_jaxpr(lambda p, x: _moe().apply({"params": p}, x, jnp.zeros((EXPERTS,))))(moe_params, x)
    tokens, largest = BATCH * SEQ, BATCH * SEQ * TOP_K * 64

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield var.aval.shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    for shape in shapes(jaxpr.jaxpr):
        assert int(np.prod(shape)) <= largest, shape
        if EXPERTS in shape and len(shape) >= 2 and tokens in shape:
            assert shape == (tokens, EXPERTS), shape


def test_the_routed_leaves_are_what_the_expert_rule_places(params):
    from sav_tpu.parallel.sharding import DEFAULT_EP_RULES, param_path_specs

    specs = param_path_specs(params, DEFAULT_EP_RULES)
    experts = specs["layer_1"]["moe"]["experts"]
    for spec in (experts["fc1"]["gate_experts_w1"], experts["fc1"]["up_experts_w1"], experts["fc2"]["experts_w2"]):
        assert spec[0] == "expert"
    assert not any(specs["layer_1"]["moe"]["shared"]["fc2"]["kernel"])


def test_int8_reaches_the_projections_and_the_experts(params, tokens):
    float_loss = program_loss(build(), params, tokens)
    model = build(quant="int8")
    out, _ = model.apply(
        {"params": params, "batch_stats": {"select_bias": jnp.zeros((3, EXPERTS))}}, tokens[:, :-1],
        is_training=True, targets=tokens[:, 1:], mutable=["losses"], rngs={"quant": jax.random.PRNGKey(0)},
    )
    quant_loss = mtp_lm_loss(out["ce"], out["ce_mtp"], LAMBDA)[0]
    assert 1e-5 < abs(float(quant_loss) - float(float_loss)) / float(float_loss) < 0.05
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, 64))
    p = params["layer_1"]["moe"]
    plain = _moe().apply({"params": p}, x, jnp.zeros((EXPERTS,)))[0]
    rounded = _moe(quant="int8").apply({"params": p}, x, jnp.zeros((EXPERTS,)), rngs={"quant": jax.random.PRNGKey(0)})[0]
    assert 1e-4 < float(jnp.max(jnp.abs(plain - rounded))) / float(jnp.max(jnp.abs(plain))) < 0.1


