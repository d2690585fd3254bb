"""The delta rule with a decay a key lane (Kimi delta attention,
arXiv:2510.26692): ``ops/gated_delta.py``'s chunked form against the rule a
token at a time, in float32 on the CPU.

Tolerances. Both sides compute in float32, in different orders (sub-blocks of
16 rows with a reference row each, a triangular inverse by doubling and a scan
over chunks against one token at a time): ``TIGHT`` = 2e-5 of the compared
tensor's largest entry for outputs and states. A gradient is held to 1e-4 of
the LARGEST gradient among the five operands' (at ``g`` = the bound the state
forgets within a token and ``dg`` is e^-5 of the others': its own largest
entry is no scale to hold it to). The channel-averaged decay, the
mathematics a scalar-decay kernel would compute, has to differ by a thousand
times ``TIGHT``.

The kernels of the state-free part (``_prepare_by_lane_in_vmem``: two chunks
of a head side by side a trip) run here in the Pallas interpreter, called past
the rule that picks the path (``rule_form`` says ``xla`` on a CPU and for the
toy key heads above), at key heads of 128 lanes and 2, 4 and 16 chunks a grid
step (1, 2 and 8 pairs), and are held to the same tolerances against the rule a
token at a time and to a tenth of them against XLA's form, which states the
same arithmetic in another order."""

import functools

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sav_tpu.ops import attention as attention_ops  # noqa: E402
from sav_tpu.ops.gated_delta import (  # noqa: E402
    CHUNK, SUB_BLOCK, _by_chunk, _chunked, _prepare_by_lane, _prepare_by_lane_in_vmem,
    gated_delta_rule, gated_delta_rule_recurrent, rule_form,
)

TIGHT = 2e-5
BOUND = -5.0  # the public config's kda_lower_bound
BATCH, HEADS, DK, DV = 2, 3, 32, 16


def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def operands(length, gate, seed=0, dtype=jnp.float32, batch=BATCH, heads=HEADS, dk=DK):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = l2(jax.random.normal(ks[0], (batch, length, heads, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[1], (batch, length, heads, dk)))
    v = jax.random.normal(ks[2], (batch, length, heads, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (batch, length, heads)))
    g = {
        "bound": jnp.full((batch, length, heads, dk), BOUND),  # every channel and token at the gate's lower bound
        "zero": jnp.zeros((batch, length, heads, dk)),  # no decay at all: the plain delta rule
        "random": BOUND * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (batch, length, heads, dk))),
    }[gate]
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def close(got, want, tol=TIGHT, scale=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) <= tol * (scale or float(np.max(np.abs(want))))


def scalar_loss(rule):
    def loss(*args):
        out, state = rule(*args)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))) + jnp.sum(jnp.square(state))
    return loss


@pytest.mark.parametrize("length", [128, 150, 40], ids=["whole_chunks", "ragged", "under_a_chunk"])
@pytest.mark.parametrize("gate", ["bound", "zero", "random"])
def test_the_chunked_rule_is_the_rule_a_token_at_a_time(gate, length):
    args = operands(length, gate)
    out, state = jax.jit(gated_delta_rule)(*args)
    want_out, want_state = jax.jit(gated_delta_rule_recurrent)(*args)
    assert out.shape == (BATCH, length, HEADS, DV) and state.shape == (BATCH, HEADS, DK, DV)
    assert bool(jnp.all(jnp.isfinite(out))) and close(out, want_out) and close(state, want_state)


@pytest.mark.parametrize("length", [128, 150], ids=["whole_chunks", "ragged"])
@pytest.mark.parametrize("gate", ["bound", "zero", "random"])
def test_the_gradients_of_all_five_operands(gate, length):
    args = operands(length, gate, seed=1)
    got = jax.jit(jax.grad(scalar_loss(gated_delta_rule), argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(scalar_loss(gated_delta_rule_recurrent), argnums=(0, 1, 2, 3, 4)))(*args)
    largest = max(float(jnp.max(jnp.abs(w))) for w in want)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g))), name
        assert close(g, w, 1e-4, scale=largest), name
    if gate != "bound":  # there dg is e^-5 of the others': held by the common scale above, and by its own here
        assert close(got[3], want[3], 1e-3)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_every_chunk_length_gives_the_same(chunk):
    args = operands(96, "random", seed=2)
    out, state = gated_delta_rule(*args, chunk=chunk)
    want_out, want_state = gated_delta_rule_recurrent(*args)
    assert close(out, want_out) and close(state, want_state)


def test_a_chunk_shorter_than_a_sub_block_is_one_sub_block():
    args = operands(24, "bound", seed=3)
    out, state = gated_delta_rule(*args, chunk=8)  # gcd(8, 16) = 8 rows a sub-block
    want_out, want_state = gated_delta_rule_recurrent(*args)
    assert SUB_BLOCK == 16 and CHUNK % SUB_BLOCK == 0
    assert close(out, want_out) and close(state, want_state)


@pytest.mark.parametrize("seed", [0, 1])
def test_equal_lanes_are_the_scalar_decay(seed):
    """A scalar decay is the vector with equal lanes: the same numbers from
    ``g [B, L, H]`` and from it repeated over the key lanes."""
    q, k, v, _, beta = operands(150, "zero", seed)
    g = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(seed + 7), (BATCH, 150, HEADS)))
    out, state = gated_delta_rule(q, k, v, jnp.broadcast_to(g[..., None], g.shape + (DK,)), beta)
    want_out, want_state = gated_delta_rule(q, k, v, g, beta)
    assert close(out, want_out) and close(state, want_state)
    rec_out, rec_state = gated_delta_rule_recurrent(q, k, v, jnp.broadcast_to(g[..., None], g.shape + (DK,)), beta)
    want_rec, _ = gated_delta_rule_recurrent(q, k, v, g, beta)
    assert close(rec_out, want_rec, 1e-6) and close(rec_state, want_state)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_decay_averaged_over_a_heads_channels_is_another_rule(seed):
    """What a scalar-decay kernel would silently compute of a vector decay:
    it must NOT pass for the rule, by a thousand times the tolerance."""
    q, k, v, g, beta = operands(128, "random", seed)
    want, _ = gated_delta_rule_recurrent(q, k, v, g, beta)
    averaged = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    for rule in (gated_delta_rule, gated_delta_rule_recurrent):
        got, _ = rule(q, k, v, averaged, beta)
        assert not close(got, want, 1000 * TIGHT)
    got, _ = gated_delta_rule(q, k, v, jnp.mean(g, axis=-1), beta)  # the scalar form itself
    assert not close(got, want, 1000 * TIGHT)


def test_causality():
    q, k, v, g, beta = operands(128, "random", seed=4)
    out, _ = gated_delta_rule(q, k, v, g, beta)
    cut = 70  # inside the second chunk, inside a sub-block
    bump = lambda x: x.at[:, cut:].add(1.0)
    k_later = k.at[:, cut:].set(l2(k[:, cut:] + 1.0))
    later, _ = gated_delta_rule(bump(q), k_later, bump(v), g.at[:, cut:].set(BOUND), beta.at[:, cut:].set(0.5))
    assert np.allclose(np.asarray(later[:, :cut]), np.asarray(out[:, :cut]), rtol=0, atol=1e-9)
    assert not np.allclose(np.asarray(later[:, cut:]), np.asarray(out[:, cut:]))


@pytest.mark.parametrize("gate", ["bound", "random"])
def test_bfloat16_operands_stay_finite_at_the_bound_and_near_the_float32_result(gate):
    args = operands(128, gate, seed=5, dtype=jnp.bfloat16)
    out, state = gated_delta_rule(*args)
    want, want_state = gated_delta_rule_recurrent(*args)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32)))) and bool(jnp.all(jnp.isfinite(state)))
    grads = jax.grad(scalar_loss(gated_delta_rule), argnums=(0, 1, 2, 3, 4))(*args)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in grads)
    assert close(out, want, 3e-2) and close(state, want_state, 3e-2)  # bfloat16 operands, float32 sums


VECTOR = {"decay": "vector"}
RULE_FORMS = [
    # (chunks, chunk, d_k, on a TPU) -> the form, one key head a value head
    ((64, 64, 128, True), {"rule": "kernel", **VECTOR, "chunk_tile": 16}),  # the vector-decay hybrid's cell
    ((6, 64, 128, True), {"rule": "kernel", **VECTOR, "chunk_tile": 6}),  # one tile holds every pair
    ((32, 128, 256, True), {"rule": "kernel", **VECTOR, "chunk_tile": 16}),
    ((64, 64, 128, False), {"rule": "xla", **VECTOR, "refused": "non-TPU backend"}),
    ((64, 64, 32, True), {"rule": "xla", **VECTOR, "refused": "key head 32 is not whole lane tiles"}),
    ((64, 48, 128, True), {"rule": "xla", **VECTOR, "refused": "chunk 48 is not a power of two of whole 16-row tiles"}),
    ((63, 64, 128, True), {"rule": "xla", **VECTOR, "refused": "63 chunks do not pair up"}),
    ((24, 64, 128, True), {"rule": "xla", **VECTOR, "refused": "24 chunks are not whole tiles of 16"}),
    ((64, 32, 128, True), {"rule": "xla", **VECTOR, "refused": "two chunks side by side x chunk 32 = 64 lanes"}),
    ((64, 256, 128, True), {"rule": "xla", **VECTOR, "refused": "two chunks side by side x chunk 256 = 512 lanes"}),
]


@pytest.mark.parametrize("shape,form", RULE_FORMS, ids=[str(shape) for shape, _ in RULE_FORMS])
def test_the_forms_record_says_what_refused_the_kernels(shape, form):
    *sizes, on_tpu = shape
    assert rule_form(*sizes, 1, by_lane=True, on_tpu=on_tpu) == form  # the scalar form's records: test_gated_delta.py


def test_the_dispatch_log_records_the_vector_rules_form(monkeypatch):
    """One record a traced shape: ``xla`` with what refused on this backend,
    ``kernel`` with its tile where a TPU would run it (nothing runs here: the
    trace alone writes the record), each with ``decay: vector``; the scalar
    form's record is the one it had."""
    args = operands(320, "zero", batch=1, heads=2, dk=128)  # lengths no other test traces: a cached trace logs nothing
    common = {"op": "gated_delta_rule", "shape": [1, 320, 2, 128], "value_heads": 2, "chunk": 64, "dtype": "float32"}
    attention_ops.clear_dispatch_log()
    jax.eval_shape(lambda *a: gated_delta_rule(*a), *args)  # five chunks
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    jax.eval_shape(lambda *a: gated_delta_rule(*a), *args)  # still five: they do not pair up
    longer = operands(384, "zero", batch=1, heads=2, dk=128)
    out, state = jax.eval_shape(lambda *a: gated_delta_rule(*a), *longer)
    assert out.shape == (1, 384, 2, DV) and state.shape == (1, 2, 128, DV)
    q, k, v, g, beta = longer
    jax.eval_shape(lambda *a: gated_delta_rule(*a), q, k, v, g[..., 0], beta)
    log = [r for r in attention_ops.snapshot_dispatch_log() if r["op"] == "gated_delta_rule"]
    attention_ops.clear_dispatch_log()
    assert log == [
        {**common, **VECTOR, "rule": "xla", "refused": "non-TPU backend"},
        {**common, "shape": [1, 384, 2, 128], **VECTOR, "rule": "kernel", "chunk_tile": 6},
        {**common, "shape": [1, 384, 2, 128], "rule": "xla",
         "refused": "1 value heads a key head x chunk 64 = 64 lanes"},
    ]


def test_a_vector_decay_takes_one_key_head_a_value_head():
    q, k, v, g, beta = operands(64, "zero")
    with pytest.raises(ValueError, match="gated delta rule"):
        gated_delta_rule(q[:, :, :1], k[:, :, :1], v, g, beta)  # grouped heads
    with pytest.raises(ValueError, match="gated delta rule"):
        gated_delta_rule(q, k, v, g[..., :8], beta)  # lanes that are not the key's


# ---------------------------------------------------------------------------
# The kernels, in the interpreter, past the rule that picks the path.
# ---------------------------------------------------------------------------

# (length, chunk, chunks a grid step): whole chunks in two grid steps of a
# pair; a padded last chunk with two pairs a step; eight pairs a step, the
# cell's tile, ragged; chunks of one sub-block, three steps.
KERNEL_LENGTHS = [(256, 64, 2), (250, 64, 4), (1000, 64, 16), (90, 16, 2)]
KERNEL_IDS = ["whole_1_pair", "ragged_2_pairs", "ragged_8_pairs", "one_sub_block"]


def in_vmem(tile):
    """The chunked rule with its state-free part in the kernels, interpreted,
    ``tile`` chunks a grid step."""
    prepare = functools.partial(_prepare_by_lane_in_vmem, tile=tile, interpret=True)
    return jax.jit(functools.partial(_chunked, prepare), static_argnames="chunk")


def wide_operands(length, gate, seed=0, dtype=jnp.float32):
    return operands(length, gate, seed, dtype, batch=1, heads=2, dk=128)


def gradients(rule, args):
    return jax.jit(jax.grad(scalar_loss(rule), argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("length,chunk,tile", KERNEL_LENGTHS, ids=KERNEL_IDS)
@pytest.mark.parametrize("gate", ["bound", "zero", "random"])
def test_the_kernels_rule_is_the_rule_a_token_at_a_time_and_xlas_form(gate, length, chunk, tile):
    args = wide_operands(length, gate, seed=6)
    out, state = in_vmem(tile)(*args, chunk=chunk)
    want_out, want_state = jax.jit(gated_delta_rule_recurrent)(*args)
    assert out.shape == want_out.shape and bool(jnp.all(jnp.isfinite(out)))
    assert close(out, want_out) and close(state, want_state)
    xla_out, xla_state = jax.jit(gated_delta_rule, static_argnames="chunk")(*args, chunk=chunk)
    assert close(out, xla_out, TIGHT / 10) and close(state, xla_state, TIGHT / 10)


@pytest.mark.parametrize("length,chunk,tile", KERNEL_LENGTHS[:2] + KERNEL_LENGTHS[3:], ids=KERNEL_IDS[:2] + KERNEL_IDS[3:])
@pytest.mark.parametrize("gate", ["bound", "zero", "random"])
def test_the_kernels_gradients_of_all_five_operands(gate, length, chunk, tile):
    """Through the backward kernel (dq, dk, dg a lane, dbeta) and the scan's
    transpose (dv, and the cotangents the kernel is handed)."""
    args = wide_operands(length, gate, seed=7)
    got = gradients(functools.partial(in_vmem(tile), chunk=chunk), args)
    want = gradients(gated_delta_rule_recurrent, args)
    xla = gradients(functools.partial(gated_delta_rule, chunk=chunk), args)
    largest = max(float(jnp.max(jnp.abs(w))) for w in want)
    for name, g, w, x in zip(("q", "k", "v", "g", "beta"), got, want, xla):
        assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g))), name
        assert close(g, w, 1e-4, scale=largest) and close(g, x, 1e-5, scale=largest), name
    if gate != "bound":  # as above: there dg is e^-5 of the others'
        assert close(got[3], want[3], 1e-3) and close(got[3], xla[3], 1e-4)


def test_the_kernels_gradients_at_the_cells_tile():
    """Eight pairs a grid step and two steps, against XLA's form (the rule a
    token at a time takes minutes to transpose at this length)."""
    args = wide_operands(2048, "random", seed=8)
    got, xla = gradients(functools.partial(in_vmem(16), chunk=CHUNK), args), gradients(gated_delta_rule, args)
    largest = max(float(jnp.max(jnp.abs(x))) for x in xla)
    for name, g, x in zip(("q", "k", "v", "g", "beta"), got, xla):
        assert close(g, x, 1e-5, scale=largest), name
    assert close(got[3], xla[3], 1e-4)


@pytest.mark.parametrize("gate", ["bound", "random"])
def test_the_kernels_results_are_xlas_on_bfloat16_operands(gate):
    """The state-free part alone, where the two programs round alike: ``T
    beta`` and the masked ``Q K^T`` to a bfloat16 unit in the last place, the
    four gradients to the rounding of the bfloat16 results (``d gamma`` is
    float32 in both)."""
    q, k, _, g, beta = wide_operands(256, gate, seed=9, dtype=jnp.bfloat16)
    q, k, g, beta = (_by_chunk(x, 4, 64) for x in (q, k, g, beta))
    weights = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 1, 2, 64, 64))

    def scalar(prepare):
        def f(*a):
            solved, inside, gamma = prepare(*a, 1)
            assert solved.dtype == inside.dtype == jnp.bfloat16 and gamma.dtype == jnp.float32
            return jnp.sum(weights[0] * solved) + jnp.sum(weights[1] * inside) + jnp.sum(jnp.sin(gamma)), (solved, inside)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True))

    (_, got), got_grads = scalar(functools.partial(_prepare_by_lane_in_vmem, tile=2, interpret=True))(q, k, g, beta)
    (_, want), want_grads = scalar(_prepare_by_lane)(q, k, g, beta)
    for a, b in zip(got, want):
        assert close(a.astype(jnp.float32), b.astype(jnp.float32), 2 ** -7)
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape and close(a.astype(jnp.float32), b.astype(jnp.float32), 2e-2)


def test_bfloat16_operands_through_the_kernels_stay_near_the_float32_rule():
    args = wide_operands(250, "random", seed=5, dtype=jnp.bfloat16)
    out, state = in_vmem(4)(*args, chunk=CHUNK)
    want, want_state = gated_delta_rule_recurrent(*args)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert close(out, want, 3e-2) and close(state, want_state, 3e-2)


def test_an_ill_conditioned_chunk_goes_through_the_forward_kernel():
    """Keys that all but coincide, every ``beta`` 1 and no decay: the system's
    entries are ``k_i . k_j`` in 0.9 to 1, and ``T beta`` is its inverse, in
    both chunks of the pair."""
    chunk, dk = 64, 128
    k = l2(1.0 + 0.25 * jax.random.normal(jax.random.PRNGKey(3), (2, 1, 1, chunk, dk)))
    g, beta = jnp.zeros((2, 1, 1, chunk, dk)), jnp.ones((2, 1, 1, chunk))
    solved, inside, gamma = _prepare_by_lane_in_vmem(k, k, g, beta, 1, tile=2, interpret=True)
    for side in range(2):
        pairs = np.asarray(jnp.einsum("id,jd->ij", k[side, 0, 0], k[side, 0, 0]), np.float64)
        assert np.min(np.tril(pairs, -1) + np.triu(np.ones_like(pairs))) > 0.85
        want = np.linalg.inv(np.eye(chunk) + np.tril(pairs, -1))
        assert np.abs(np.linalg.matrix_power(np.tril(pairs, -1), 32)).max() > 1e12  # a term of the series
        assert np.allclose(np.asarray(solved[side, 0, 0], np.float64), want, atol=1e-4 * np.abs(want).max())
        assert np.allclose(np.asarray(inside[side, 0, 0]), np.tril(pairs), atol=1e-5)
    assert not np.any(np.asarray(gamma))
