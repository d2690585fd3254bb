"""The layers the JoyAI-LLM-Flash family brought, against its plain float32
reference and against each other, at toy sizes on the CPU: the latent
attention block, rotary on adjacent pairs of a slice of the head, the flash
kernel with a value head narrower than the query's (interpret mode), the
dropless sorted expert path against a loop (uneven routing, an expert with
no token, every token on the same experts), and one chip's share of an
expert-parallel layer (sixteen shares and the shared expert once are the uncut
layer; with a share held, the bounded buffers and the overflow pass against
the loop, the sizes of what the layer builds and the scopes its operations
lie under). ``test_joyai.py`` holds the model, the task and the trainer; the
toy sizes and the tolerance are its."""

import functools
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
from sav_tpu.models.layers import moe as moe_layers
from sav_tpu.models.layers.moe import routed_row_bound
from sav_tpu.ops import rows_to_tokens as sums
from sav_tpu.ops.attention import snapshot_dispatch_log, xla_attention
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


@pytest.fixture()
def sum_form_of(monkeypatch):
    """``sum_form_of("kernel")``: the layer's sum of rows by token takes the
    form a TPU would (the Mosaic kernel, here in the interpreter) wherever the
    shapes allow it; ``"xla"`` leaves the CPU's form. The kernel's cases run
    at a width of their own (whole lane tiles), so no jitted loop traced for
    one form is reused for the other."""
    def force(form):
        if form == "kernel":
            monkeypatch.setattr(sums, "sum_form", functools.partial(sums.sum_form, on_tpu=True))
        return 128 if form == "kernel" else 64

    return force


def _drawn(layer, x, seed=5):
    abstract = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x, jnp.zeros((EXPERTS,))))["params"]
    return _routed_at_fan_in_scale(weights.draw_params(abstract, seed))


ROUTINGS = {
    # A large bias on few experts sends every token there; an expert whose
    # bias is -10 sees no token.
    "seeded": np.zeros(EXPERTS),
    "uneven": np.where(np.arange(EXPERTS) < 6, 0.5, 0.0),
    "an_expert_with_no_token": np.where(np.arange(EXPERTS) == 3, -10.0, 0.0),
    "all_tokens_on_the_same_experts": np.where(np.arange(EXPERTS) < TOP_K, 10.0, 0.0),
}


@pytest.mark.parametrize("case,form", [(case, "xla") for case in sorted(ROUTINGS)] + [("uneven", "kernel")])
def test_sorted_path_matches_the_loop_over_experts(moe_params, sum_form_of, case, form):
    bias = jnp.asarray(ROUTINGS[case], jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, sum_form_of(form)))
    if form == "kernel":
        moe_params = _drawn(_moe(), x)

    def run(p, x):
        return _moe().apply({"params": p}, x, bias)

    (y, counts, balance, _), want = run(moe_params, x), _moe_reference(x, moe_params, bias)
    for b in range(BATCH):
        assert close(y[b], want[b][0]) and np.array_equal(np.asarray(counts[b]), np.asarray(want[b][1]))
    assert float(balance) == pytest.approx(float(np.mean([w[2] for w in want])), rel=1e-5)
    assert {r["sum"] for r in snapshot_dispatch_log() if r.get("op") == "rows_to_tokens" and r["shape"][1] == x.shape[-1]} == {form}
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
    uncut, counts, balance, _ = whole.apply({"params": p}, x, bias)
    from sav_tpu.models.layers import GatedFFBlock

    shared_out = GatedFFBlock(hidden_ch=32).apply({"params": p["shared"]}, x.reshape(64, 64)).reshape(x.shape)
    routed = jnp.zeros_like(uncut)
    for chip in range(chips):
        held = (chip * experts // chips, experts // chips)
        cut = jax.tree.map(lambda a: a, p)
        for group, leaf in (("fc1", "gate_experts_w1"), ("fc1", "up_experts_w1"), ("fc2", "experts_w2")):
            cut["experts"][group][leaf] = p["experts"][group][leaf][held[0]:held[0] + held[1]]
        part, part_counts, part_balance, _ = _moe(held, experts=experts, k=k).apply({"params": cut}, x, bias)
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
    y, counts, _, _ = _moe().apply({"params": moe_params}, x, bias)
    y_all, counts_all, _, _ = _moe((0, EXPERTS)).apply({"params": moe_params}, x, bias)
    assert np.array_equal(np.asarray(y), np.asarray(y_all)) and np.array_equal(np.asarray(counts), np.asarray(counts_all))
    with pytest.raises(ValueError, match="do not fit"):
        _moe((8, 12)).init(jax.random.PRNGKey(0), x, bias)


def _shapes(jaxpr):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval.shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_expert_path_builds_nothing_of_tokens_by_experts_by_more(moe_params, sum_form_of, form):
    """No dispatch tensor: beyond the [T, E] scores nothing has both a token
    axis and an expert axis, and the largest value is [T k, D], the Mosaic
    sum's scratch and its few integers included."""
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, sum_form_of(form)))
    if form == "kernel":
        moe_params = _drawn(_moe(), x)
    jaxpr = jax.make_jaxpr(lambda p, x: _moe().apply({"params": p}, x, jnp.zeros((EXPERTS,))))(moe_params, x)
    tokens, largest = BATCH * SEQ, BATCH * SEQ * TOP_K * x.shape[-1]
    assert ("pallas_call" in str(jaxpr)) == (form == "kernel")

    for shape in _shapes(jaxpr.jaxpr):
        assert int(np.prod(shape)) <= largest, shape
        if EXPERTS in shape and len(shape) >= 2 and tokens in shape:
            assert shape == (tokens, EXPERTS), shape


# ------------------------------------------------- the sum of rows by token


def _sorted_window(index: int, count: int = 160, tokens: int = 128, groups: int = 4):
    """A window of ``count`` sorted rows as the layer's one sort leaves them:
    ``groups`` held experts, inside each the tokens ascending. Token 0 has a
    row in every group, token 1 in one, token 2 in none, and no row lands in
    the second tile of 32 tokens; window 1 starts inside the last group's run
    and ends past it."""
    picked = np.random.default_rng(3).random((tokens, groups)) < 0.5
    picked[0], picked[1], picked[2], picked[32:64] = True, [True] + [False] * (groups - 1), False, False
    order = np.concatenate([np.flatnonzero(picked[:, g]) * groups + g for g in range(groups)])
    assert count < order.size < 2 * count
    order = jnp.asarray(np.pad(order, (0, 2 * count - order.size)), jnp.int32)
    routing, live, sizes = moe_layers._sorted_chunk(order, jnp.asarray(picked.sum(0), jnp.int32), index, count)
    return routing // groups, live, sizes, picked


@pytest.mark.parametrize("rows_of", ["float32_rows", "bfloat16_rows_with_a_weight"])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_sum_of_rows_by_token_is_segment_sum_and_the_gathers_transpose(monkeypatch, sum_form_of, form, rows_of):
    """Both forms of ``_tokens_of_rows`` against the sum written out and
    against JAX's own derivative of the gather, in values and in gradients:
    tokens with no, one and ``k`` live rows, a tile no row lands in, ranges
    that cross a tile's edge, a window that starts mid-run, and NaN past the
    last group that must not reach the result."""
    monkeypatch.setattr(sums, "TOKEN_TILE", 32)  # four tiles of these 128 tokens
    sum_form_of(form)
    tokens, dim, dtype = 128, 128, jnp.float32 if rows_of == "float32_rows" else jnp.bfloat16
    for index in (0, 1):
        token, live, sizes, picked = _sorted_window(index)
        keys = jax.random.split(jax.random.PRNGKey(index), 3)
        rows = jnp.where(live[:, None], jax.random.normal(keys[0], (live.shape[0], dim)), jnp.nan).astype(dtype)
        weight = None if rows_of == "float32_rows" else jax.random.normal(keys[1], live.shape)
        got = moe_layers._tokens_of_rows(rows, weight, token, live, sizes, tokens, jnp.float32)
        terms = jnp.where(live[:, None], rows.astype(jnp.float32) * (1.0 if weight is None else weight[:, None]), 0.0)
        want = np.zeros((tokens, dim), np.float64)
        np.add.at(want, np.asarray(token)[np.asarray(live)], np.asarray(terms, np.float64)[np.asarray(live)])
        assert np.all(np.isfinite(np.asarray(got))) and np.max(np.abs(np.asarray(got) - want)) <= 1e-6 * np.max(np.abs(want))
        assert not np.any(np.asarray(got[32:64])) and not np.any(np.asarray(got[2]))
        if index == 0:
            assert int(jnp.sum(live & (token == 0))) == 4 and int(jnp.sum(live & (token == 1))) == 1
        else:
            assert 0 < int(jnp.sum(live)) < live.shape[0] and int(sizes[0]) == 0 and 0 < int(sizes[-1]) < int(jnp.sum(picked[:, -1]))
        gather = lambda x: jnp.where(live[:, None], jnp.take(x, token, axis=0), 0)
        (transposed,) = jax.vjp(gather, jnp.zeros((tokens, dim)))[1](terms)
        assert float(jnp.max(jnp.abs(got - transposed))) <= 1e-6 * float(jnp.max(jnp.abs(transposed)))
        # The gradients: the cotangent's gathered rows (times the other factor), zeros at dead rows.
        g = jax.random.normal(keys[2], (tokens, dim))
        finite = jnp.where(live[:, None], rows, 0)  # (JAX's derivative of the written-out sum would carry the NaN)
        by_hand = lambda rows, weight: jnp.sum(g * jax.ops.segment_sum(
            rows.astype(jnp.float32) * (1.0 if weight is None else weight[:, None]), jnp.where(live, token, tokens),
            num_segments=tokens))
        layer = lambda rows, weight: jnp.sum(g * moe_layers._tokens_of_rows(rows, weight, token, live, sizes, tokens, jnp.float32))
        argnums = (0,) if weight is None else (0, 1)
        for a, b in zip(jax.grad(layer, argnums)(rows, weight), jax.grad(by_hand, argnums)(finite, weight)):
            assert a.dtype == b.dtype and np.all(np.isfinite(np.asarray(a, np.float32)))
            assert float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) <= 1e-2 * float(jnp.max(jnp.abs(b)))
            assert not np.any(np.asarray(a, np.float32)[~np.asarray(live)])
        # dispatch's backward: bfloat16 cotangent rows in, their float32 sum cast once.
        if rows_of != "float32_rows":
            back = moe_layers._tokens_of_rows(rows, None, token, live, sizes, tokens, jnp.bfloat16)
            plain = moe_layers._sums.sum_xla(finite, None, token, live, tokens, jnp.bfloat16)
            assert back.dtype == jnp.bfloat16 and np.array_equal(np.asarray(back, np.float32), np.asarray(plain, np.float32))


def test_sum_form_takes_the_kernel_on_a_tpu_at_whole_tiles_and_notes_it():
    """The rule, from the backend and the shapes alone, and its record in the
    dispatch log."""
    from sav_tpu.ops.attention import clear_dispatch_log

    assert sums.sum_form(32768, 32768, 2048, 8, jnp.bfloat16, on_tpu=True) == {"sum": "kernel", "tile": 512, "unit": 16}
    assert sums.sum_form(8192, 8192, 3584, 8, jnp.float32, on_tpu=True)["tile"] == 512
    assert sums.sum_form(8192, 8192, 8192, 8, jnp.float32, on_tpu=True)["tile"] == 256  # no more than 2 M elements
    assert sums.sum_form(512, 96, 128, 4, jnp.float32, on_tpu=True)["tile"] == 32
    refusals = {
        "non-TPU backend": sums.sum_form(32768, 32768, 2048, 8, jnp.bfloat16, on_tpu=False),
        "lane tiles": sums.sum_form(256, 64, 64, 16, jnp.float32, on_tpu=True),
        "8-row tiles": sums.sum_form(256, 36, 128, 16, jnp.float32, on_tpu=True),
        "16-row copies": sums.sum_form(24, 8, 128, 16, jnp.float32, on_tpu=True),  # init's handful of rows
        "float16": sums.sum_form(256, 64, 128, 16, jnp.float16, on_tpu=True),
        "scalars in SMEM": sums.sum_form(1 << 17, 1 << 15, 128, 16, jnp.float32, on_tpu=True),
    }
    for why, form in refusals.items():
        assert form["sum"] == "xla" and why in form["refused"], form
    assert sums.sum_form(256, 64, 128, 16, jnp.float32)["refused"] == "non-TPU backend"  # this process's backend
    clear_dispatch_log()
    token, live, sizes, _ = _sorted_window(0)
    rows = jnp.zeros((live.shape[0], 128), jnp.bfloat16)
    moe_layers._tokens_of_rows(rows, jnp.ones(live.shape), token, live, sizes, 128, jnp.float32)
    moe_layers._tokens_of_rows(rows, None, token, live, sizes, 128, jnp.bfloat16)
    records = [r for r in snapshot_dispatch_log() if r.get("op") == "rows_to_tokens"]
    assert records == [
        {"op": "rows_to_tokens", "shape": [160, 128], "tokens": 128, "dtype": "bfloat16", "result": result,
         "weighted": weighted, "sum": "xla", "refused": "non-TPU backend"}
        for result, weighted in (("float32", True), ("bfloat16", False))
    ]


# ----------------------------------------- a share held: bound and overflow

HELD = (4, 4)  # experts 4..7 of 16; 256 tokens x 4 routings, so the buffers hold 512 of 1,024 rows
WIDE_BATCH, WIDE_SEQ = 2, 128
BOUND = 512


def _held_params(held=HELD, seed=5):
    return _drawn(_moe(held), jnp.zeros((WIDE_BATCH, WIDE_SEQ, 64)), seed)


def _steered(on_all_held: int, on_one_held: int):
    """Input ``[2, 128, 64]`` and parameters whose router reads the first two
    features: ``on_all_held`` tokens (scattered over both sequences) select
    the four held experts, ``on_one_held`` the first of them beside three
    absent ones, the others absent experts only: ``4 on_all_held +
    on_one_held`` rows on the held experts."""
    tokens = WIDE_BATCH * WIDE_SEQ
    kind = np.zeros(tokens, np.int32)
    kind[: on_all_held + on_one_held] = np.r_[np.ones(on_all_held, np.int32), np.full(on_one_held, 2, np.int32)]
    kind = np.random.default_rng(0).permutation(kind)
    x = 0.1 * np.asarray(jax.random.normal(jax.random.PRNGKey(7), (tokens, 64)))
    x[:, 0] = np.where(kind == 1, 3.0, -3.0)
    x[:, 1] = np.where(kind == 2, 3.0, 0.0)
    p = _held_params()
    held = (np.arange(EXPERTS) >= HELD[0]) & (np.arange(EXPERTS) < sum(HELD))
    router = np.array(p["route"]["kernel"])
    router[0] = np.where(held, 1.0, -1.0)
    router[1] = np.where(np.arange(EXPERTS) == HELD[0], 3.0, 0.0)
    p = {**p, "route": {"kernel": jnp.asarray(router)}}
    return jnp.asarray(x, jnp.float32).reshape(WIDE_BATCH, WIDE_SEQ, 64), p


ONTO_HELD = np.where((np.arange(EXPERTS) >= HELD[0]) & (np.arange(EXPERTS) < sum(HELD)), 10.0, 0.0)
# name -> (tokens on all four held experts, tokens on one, selection bias, rows on the held experts)
OVERFLOWS = {
    "below_the_bound": (100, 3, 0.0, 403),
    "exactly_the_bound": (128, 0, 0.0, BOUND),
    "one_row_over": (128, 1, 0.0, BOUND + 1),
    "every_routing_on_held_experts": (10, 5, ONTO_HELD, 1024),
}


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_three_chunks_the_last_partly_filled_match_the_loop(sum_form_of, form):
    """320 tokens x 2 routings of which 2 of 16 experts are held: buffers of
    one tile (256 rows) for 640 routings, all of them on the held experts:
    the common pass, a whole chunk of the overflow pass and half a chunk.
    With the kernel form the sums inside both loops are the Mosaic call's,
    their window's offset a traced operand."""
    held, k, tokens = (4, 2), 2, 320
    assert routed_row_bound(tokens * k, held[1], EXPERTS) == 256
    x = jax.random.normal(jax.random.PRNGKey(7), (2, tokens // 2, sum_form_of(form)))
    layer = _moe(held, k=k)
    p = _drawn(layer, x)
    bias = jnp.asarray(np.where((np.arange(EXPERTS) >= 4) & (np.arange(EXPERTS) < 6), 10.0, 0.0), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def run(p, x):
        y, counts, _, _ = layer.apply({"params": p}, x, bias)
        return jnp.sum(y * g), counts

    def loop(p, x):
        return sum(jnp.sum(o[0] * g[b]) for b, o in enumerate(_moe_reference(x, p, bias, held=held, k=k)))

    (value, counts), got = jax.jit(jax.value_and_grad(run, (0, 1), has_aux=True))(p, x)
    assert float(jnp.sum(counts[:, 4:6])) == tokens * k
    want_value, want = jax.jit(jax.value_and_grad(loop, (0, 1)))(p, x)
    assert abs(float(value) - float(want_value)) <= TIGHT * abs(float(want_value))
    scale = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= TIGHT * scale, jax.tree_util.keystr(path)


@pytest.mark.parametrize("case", sorted(OVERFLOWS))
def test_a_held_share_matches_the_loop_at_and_beyond_the_bound(case):
    """Nothing is dropped at any routing: rows within the bound go through
    the common pass, rows beyond it through the overflow pass's chunks, and
    the sum is the reference's loop in values and in gradients."""
    on_all, on_one, bias, held_rows = OVERFLOWS[case]
    assert routed_row_bound(WIDE_BATCH * WIDE_SEQ * TOP_K, HELD[1], EXPERTS) == BOUND
    x, p = _steered(on_all, on_one)
    bias = jnp.broadcast_to(jnp.asarray(bias, jnp.float32), (EXPERTS,))

    def run(p, x):
        return _moe(HELD).apply({"params": p}, x, bias)

    (y, counts, balance, _), want = run(p, x), _moe_reference(x, p, bias, held=HELD)
    assert float(jnp.sum(counts[:, HELD[0]:sum(HELD)])) == held_rows
    for b in range(WIDE_BATCH):
        assert close(y[b], want[b][0]) and np.array_equal(np.asarray(counts[b]), np.asarray(want[b][1]))
    assert float(balance) == pytest.approx(float(np.mean([w[2] for w in want])), rel=1e-5)
    g = jax.random.normal(jax.random.PRNGKey(8), y.shape)
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(run(p, x)[0] * g) + run(p, x)[2], (0, 1)))(p, x)

    def loop(p, x):
        out = _moe_reference(x, p, bias, held=HELD)
        return sum(jnp.sum(o[0] * g[b]) for b, o in enumerate(out)) + sum(o[2] for o in out) / WIDE_BATCH

    want = jax.jit(jax.grad(loop, (0, 1)))(p, x)
    scale = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= TIGHT * scale, jax.tree_util.keystr(path)
    # The routed branch is not a rounding error of the comparison.
    assert float(jnp.max(jnp.abs(got[0]["experts"]["fc2"]["experts_w2"]))) > 1e-2 * scale


def test_the_int8_arm_takes_the_overflow_pass_with_the_same_rounding(monkeypatch):
    """Beyond the bound the int8 arm rounds rows, kernels and hidden rows as
    within it (per row and per channel), so which pass computes a row does
    not change it: the layer with one row over the bound against the same
    layer with buffers that hold every routing."""
    from sav_tpu.models.layers import moe

    x, p = _steered(128, 1)
    bias = jnp.zeros((EXPERTS,))
    rounded = _moe(HELD, quant="int8").apply({"params": p}, x, bias)[0]
    plain = _moe(HELD).apply({"params": p}, x, bias)[0]
    assert 1e-4 < float(jnp.max(jnp.abs(plain - rounded))) / float(jnp.max(jnp.abs(plain))) < 0.1
    monkeypatch.setattr(moe, "ROWS_OVER_EXPECTED", EXPERTS // HELD[1])
    assert routed_row_bound(WIDE_BATCH * WIDE_SEQ * TOP_K, HELD[1], EXPERTS) == WIDE_BATCH * WIDE_SEQ * TOP_K
    assert close(rounded, _moe(HELD, quant="int8").apply({"params": p}, x, bias)[0], 1e-5)


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_with_a_sixteenth_held_nothing_is_larger_than_the_bound(what):
    """One of 16 experts held, 256 tokens x 4 routings: the buffers are one
    tile of 256 rows, and no value, the overflow pass's loop included, has
    more than 256 x D elements (the input's own size): none of ``T k x D``,
    none of ``T x k x D``."""
    held, dim = (3, 1), 64
    x = jax.random.normal(jax.random.PRNGKey(7), (WIDE_BATCH, WIDE_SEQ, dim))
    p = _held_params(held)
    tokens = WIDE_BATCH * WIDE_SEQ
    bound = routed_row_bound(tokens * TOP_K, 1, EXPERTS)
    assert bound == 256 < tokens * TOP_K

    def forward(p, x):
        return _moe(held).apply({"params": p}, x, jnp.zeros((EXPERTS,)))[0]

    fn = forward if what == "forward" else jax.grad(lambda p, x: jnp.sum(forward(p, x)), (0, 1))
    jaxpr = jax.make_jaxpr(fn)(p, x)
    assert "while" in str(jaxpr)
    largest = 0
    for shape in _shapes(jaxpr.jaxpr):
        largest = max(largest, int(np.prod(shape)))
        assert int(np.prod(shape)) <= bound * dim, shape
    assert largest == bound * dim == tokens * dim


def _calls_by_scope(jaxpr, above=()):
    """The name stacks, outermost first, down to every ``pallas_call``."""
    for eqn in jaxpr.eqns:
        here = above + (str(eqn.source_info.name_stack), str(eqn.params.get("name", "")) * (eqn.primitive.name == "jit"))
        if eqn.primitive.name == "pallas_call":
            yield "/".join(filter(None, here))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _calls_by_scope(sub, here)


def test_the_common_pass_lies_under_its_scopes_and_in_no_loop_of_the_overflow_pass(sum_form_of):
    """What the benchmark's share and roofline readers match, on the CPU
    lowering of a held layer's gradient: every operation of the layer lies
    under ``moe`` directly followed by ``route``, ``dispatch``, ``experts``
    (its matmuls then under ``fc1`` or ``fc2``), ``combine``, ``overflow`` or
    ``shared``; the common pass is in no conditional and its only loop is
    ``searchsorted``'s, and the overflow pass's loops lie under ``overflow``,
    their bodies under the layer's label and the common pass's scopes again.
    In the form a TPU takes, the sums by token are Mosaic calls named
    ``rows_to_tokens`` under the same scopes: ``combine`` forward,
    ``dispatch`` backward, in the common pass and in a trip of either loop."""
    import flax.linen as nn

    from benchmark import tracered
    from benchmark.stepscopes import scopes_of

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, bias):
            return SparseMoEBlock(
                num_experts=EXPERTS, top_k=TOP_K, hidden_ch=32, routed_scale=2.5, experts_held=HELD, name="moe",
            )(x, bias)

    x, p = _steered(100, 3)
    bias = jnp.zeros((EXPERTS,))
    # (With its value: a gradient alone leaves the forward overflow loop dead code.)
    grad = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(Layer().apply({"params": {"moe": p}}, x, bias)[0]), (0, 1)))
    op_names = set(tracered.scopes_of_hlo(grad.lower(p, x).compile().as_text()).values())
    seen = {}
    for op_name in op_names:
        labels = scopes_of(op_name)
        if "moe" not in labels:
            continue
        inside = labels[labels.index("moe") + 1:]
        if not inside:
            assert op_name.rsplit("/", 1)[-1] == "reshape", op_name  # the module's own [B, S, D] <-> [T, D]
            continue
        assert inside[0] in ("route", "dispatch", "experts", "combine", "overflow", "shared"), op_name
        seen.setdefault(inside[0], set()).add(op_name)
        if inside[0] == "overflow":
            continue
        assert not any(label.startswith("branch_") for label in inside), op_name
        if "while" in inside or op_name.endswith("/while"):
            assert "jit(searchsorted)" in op_name, op_name
        if inside[0] == "experts":
            assert inside[1] in ("fc1", "fc2"), op_name
    assert set(seen) == {"route", "dispatch", "experts", "combine", "overflow", "shared"}
    primitives = lambda scope: {name.rsplit("/", 1)[-1] for name in seen[scope]}
    assert "gather" in primitives("dispatch") and "scatter-add" in primitives("dispatch")  # rows out, and its transpose
    assert "scatter-add" in primitives("combine") and "gather" in primitives("combine")  # the sum back, and its transpose
    assert "dot_general" in primitives("experts")
    # The overflow pass: a loop each way, the grouped matmuls and the sums inside it.
    in_loop = {name.split("/while/body/", 1)[1] for name in seen["overflow"] if "/while/body/" in name}
    assert any(name.startswith("jvp(Layer)/moe/overflow/while") for name in (n.split("/", 1)[1] for n in seen["overflow"]))
    assert any("transpose(jvp(Layer))/moe/overflow/while" in name for name in seen["overflow"])
    assert {"dot_general", "scatter-add"} <= {name.rsplit("/", 1)[-1] for name in in_loop}
    # A trip is the common pass's work and is named as it: the body opens the layer's label
    # again, so whoever reads ``moe`` followed by ``dispatch|experts|combine`` counts a trip too.
    for name in in_loop:
        labels = scopes_of(name)
        if not labels:  # the carry's own additions
            continue
        assert labels[0] == "moe", name
        inner = [label for label in labels if label != "moe"]
        assert inner[0] in ("dispatch", "experts", "combine"), name
        if inner[0] == "experts":
            assert inner[1] in ("fc1", "fc2"), name
    # The form a TPU takes, read from the jaxpr (a Mosaic call lowers for no CPU).
    x = jax.random.normal(jax.random.PRNGKey(7), (WIDE_BATCH, WIDE_SEQ, sum_form_of("kernel")))
    p = _drawn(_moe(HELD), x)
    grad = jax.value_and_grad(lambda p, x: jnp.sum(Layer().apply({"params": {"moe": p}}, x, bias)[0]), (0, 1))
    calls = list(_calls_by_scope(jax.make_jaxpr(grad)(p, x).jaxpr))
    assert calls and all(name.endswith("rows_to_tokens") and "/moe/" in name for name in calls), calls
    where = {("/overflow/" in name, "combine" if "combine" in name else "dispatch" if "dispatch" in name else None,
              name.startswith("transpose")) for name in calls}
    # (A backward trip forms the forward's sum under ``combine`` again; nobody reads it and XLA drops it.)
    assert where == {(False, "combine", False), (False, "dispatch", True), (True, "combine", False),
                     (True, "combine", True), (True, "dispatch", True)}, calls


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


