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
from sav_tpu.ops import gated_delta as rule_ops  # noqa: E402
from sav_tpu.ops.gated_delta import (  # noqa: E402
    CHUNK, SUB_BLOCK, _by_chunk, _chunked, _operands, _operands_in_vmem, _prepare_by_lane, _prepare_by_lane_in_vmem,
    _summed_by_chunk, gated_delta_rule, gated_delta_rule_from_raw, gated_delta_rule_recurrent, l2_normalise, rule_form,
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
OPERANDS_IN_VMEM = {"operands": "kernel", "operands_tile": 16}
RULE_FORMS = [
    # (chunks, chunk, d_k, on a TPU) -> the state-free part's form and the operands', one key head a value head
    ((64, 64, 128, True), {"rule": "kernel", **VECTOR, "chunk_tile": 16}, OPERANDS_IN_VMEM),  # the vector-decay hybrid's cell
    ((6, 64, 128, True), {"rule": "kernel", **VECTOR, "chunk_tile": 6},
     {"operands": "kernel", "operands_tile": 6}),  # one tile holds every pair
    ((32, 128, 256, True), {"rule": "kernel", **VECTOR, "chunk_tile": 16}, OPERANDS_IN_VMEM),
    ((64, 64, 128, False), {"rule": "xla", **VECTOR, "refused": "non-TPU backend"},
     {"operands": "xla", "operands_refused": "non-TPU backend"}),
    ((64, 64, 32, True), {"rule": "xla", **VECTOR, "refused": "key head 32 is not whole lane tiles"},
     {"operands": "xla", "operands_refused": "key head 32 is not whole lane tiles"}),
    ((64, 48, 128, True), {"rule": "xla", **VECTOR, "refused": "chunk 48 is not a power of two of whole 16-row tiles"},
     OPERANDS_IN_VMEM),
    ((64, 40, 128, True), {"rule": "xla", **VECTOR, "refused": "chunk 40 is not a power of two of whole 16-row tiles"},
     {"operands": "xla", "operands_refused": "chunk 40 is not whole 16-row tiles"}),
    ((63, 64, 128, True), {"rule": "xla", **VECTOR, "refused": "63 chunks do not pair up"},
     {"operands": "kernel", "operands_tile": 9}),  # the operands' calls pair nothing up
    ((24, 64, 128, True), {"rule": "xla", **VECTOR, "refused": "24 chunks are not whole tiles of 16"},
     {"operands": "kernel", "operands_tile": 12}),
    ((64, 32, 128, True), {"rule": "xla", **VECTOR, "refused": "two chunks side by side x chunk 32 = 64 lanes"},
     OPERANDS_IN_VMEM),
    ((64, 256, 128, True), {"rule": "xla", **VECTOR, "refused": "two chunks side by side x chunk 256 = 512 lanes"},
     OPERANDS_IN_VMEM),
]


@pytest.mark.parametrize("shape,form,operands_form", RULE_FORMS, ids=[str(shape) for shape, _, _ in RULE_FORMS])
def test_the_forms_record_says_what_refused_the_kernels(shape, form, operands_form):
    *sizes, on_tpu = shape
    # the scalar form's records: test_gated_delta.py
    assert rule_form(*sizes, 1, by_lane=True, on_tpu=on_tpu) == {**form, **operands_form}


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
        {**common, **VECTOR, "rule": "xla", "refused": "non-TPU backend", "operands": "given"},
        {**common, "shape": [1, 384, 2, 128], **VECTOR, "rule": "kernel", "chunk_tile": 6, "operands": "given"},
        {**common, "shape": [1, 384, 2, 128], "rule": "xla",
         "refused": "1 value heads a key head x chunk 64 = 64 lanes", "operands": "given"},
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

    def rule(q, k, v, g, beta, chunk):
        operands = (_by_chunk(q, chunk), _by_chunk(k, chunk), _summed_by_chunk(g, chunk))
        return _chunked(prepare, operands, operands, v, beta)

    return jax.jit(rule, static_argnames="chunk")


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
    q, k, beta = (_by_chunk(x, 64) for x in (q, k, beta))
    gamma = _summed_by_chunk(g, 64)
    weights = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 1, 2, 64, 64))

    def scalar(prepare):
        def f(*a):
            solved, inside = prepare(*a, 1)
            assert solved.dtype == inside.dtype == jnp.bfloat16
            return jnp.sum(weights[0] * solved) + jnp.sum(weights[1] * inside), (solved, inside)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True))

    (_, got), got_grads = scalar(functools.partial(_prepare_by_lane_in_vmem, tile=2, interpret=True))(q, k, gamma, beta)
    (_, want), want_grads = scalar(_prepare_by_lane)(q, k, gamma, beta)
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
    gamma, beta = jnp.zeros((2, 1, 1, chunk, dk)), jnp.ones((2, 1, 1, chunk))
    solved, inside = _prepare_by_lane_in_vmem(k, k, gamma, beta, 1, tile=2, interpret=True)
    for side in range(2):
        pairs = np.asarray(jnp.einsum("id,jd->ij", k[side, 0, 0], k[side, 0, 0]), np.float64)
        assert np.min(np.tril(pairs, -1) + np.triu(np.ones_like(pairs))) > 0.85
        want = np.linalg.inv(np.eye(chunk) + np.tril(pairs, -1))
        assert np.abs(np.linalg.matrix_power(np.tril(pairs, -1), 32)).max() > 1e12  # a term of the series
        assert np.allclose(np.asarray(solved[side, 0, 0], np.float64), want, atol=1e-4 * np.abs(want).max())
        assert np.allclose(np.asarray(inside[side, 0, 0]), np.tril(pairs), atol=1e-5)


# ---------------------------------------------------------------------------
# The rule's operands (PR 45): the normalisation of q and k, the safe gate and
# its running sum inside a chunk, from a block's own arrays. ``_operands`` is
# XLA's program (the blocks' lines of before); ``_operands_in_vmem`` the two
# kernels, here in the interpreter, past the rule that picks the path.
# ---------------------------------------------------------------------------

# What the gate's pre-activation is filled with: sigmoid(exp(A_log) (a +
# dt_bias)) is 1 to float32's last place at 40 and 2e-8 at -40, so ``g`` is
# the bound in every lane, or 0 in every lane.
GATES = {"bound": 40.0, "zero": -40.0, "random": None}
# (length, chunk, chunks a grid step): whole chunks in two grid steps; a
# padded last chunk, one step and two; chunks of one 16-row tile, three steps.
OPERAND_LENGTHS = [(256, 64, 2), (250, 64, 4), (250, 64, 2), (90, 16, 2)]
OPERAND_IDS = ["whole_2_steps", "ragged_1_step", "ragged_2_steps", "chunks_of_16"]


def raw_operands(length, gate, seed=0, dtype=jnp.float32, batch=2, heads=2, dk=128):
    """``q, k, (a, A_log, dt_bias)`` as a block has them: the convolution's
    results (a SiLU leaves a common component) and the ``f`` projection's."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, length, heads, dk)
    q, k = (jax.nn.silu(jax.random.normal(key, shape) + 0.5) for key in ks[:2])
    a = 2.0 * jax.random.normal(ks[2], shape) if GATES[gate] is None else jnp.full(shape, GATES[gate])
    a_log = jnp.log(jax.random.uniform(ks[3], (heads,), minval=0.5, maxval=4.0))
    dt_bias = jax.random.normal(ks[4], (heads * dk,))
    return q.astype(dtype), k.astype(dtype), (a.astype(dtype), a_log, dt_bias)


def in_vmem_operands(tile):
    return lambda q, k, gate, chunk: _operands_in_vmem(q, k, gate, chunk, BOUND, tile, True)


def xla_operands(q, k, gate, chunk):
    return _operands(q, k, gate, chunk, BOUND)


def operands_and_gradients(program, chunk, args, seed=11):
    """The results and the gradients of all five leaves (q, k, ``a``,
    ``A_log``, ``dt_bias``) under one weighted sum, the results' two copies
    (one a reader) weighted apart so that the backward has two cotangents of
    each to add."""
    chunks = -(-args[0].shape[1] // chunk)
    shape = (chunks,) + (args[0].shape[0], args[0].shape[2], chunk, args[0].shape[3])
    weights = jax.random.normal(jax.random.PRNGKey(seed), (2, 3) + shape)

    def f(q, k, gate):
        once, again, least = program(q, k, gate, chunk)
        total = sum(jnp.sum(w * x.astype(jnp.float32)) for w, x in zip(weights[0], once))
        total += sum(jnp.sum(w * jnp.sin(x.astype(jnp.float32))) for w, x in zip(weights[1], again))
        return total, once + (least, again)

    (_, results), grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(*args)
    return results, jax.tree.leaves(grads)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length,chunk,tile", OPERAND_LENGTHS, ids=OPERAND_IDS)
@pytest.mark.parametrize("gate", sorted(GATES))
def test_the_operands_kernels_are_xlas_program(gate, length, chunk, tile, dtype):
    """Values and all five gradients. q and k normalised are rounded once to
    their dtype in both programs (equal, or a unit in the last place apart);
    ``gamma`` is float32 in both; a bfloat16 gradient is held to its rounding."""
    args = raw_operands(length, gate, seed=12, dtype=dtype)
    (qn, kn, gamma, least, again), grads = operands_and_gradients(in_vmem_operands(tile), chunk, args)
    (want_q, want_k, want_gamma, want_least, _), want_grads = operands_and_gradients(xla_operands, chunk, args)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip((qn, kn, gamma), again))  # once a reader
    assert qn.dtype == kn.dtype == dtype and gamma.dtype == jnp.float32 and gamma.shape == qn.shape
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6
    assert close(qn.astype(jnp.float32), want_q.astype(jnp.float32), ulp)
    assert close(kn.astype(jnp.float32), want_k.astype(jnp.float32), ulp)
    assert close(gamma, want_gamma, 1e-6, scale=max(float(jnp.max(jnp.abs(want_gamma))), 1e-3))
    assert close(least, want_least, 1e-6, scale=abs(BOUND))
    largest = max(float(jnp.max(jnp.abs(w.astype(jnp.float32)))) for w in want_grads)
    for name, g, w in zip(("q", "k", "a", "A_log", "dt_bias"), grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype and bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), name
        # d A_log and d dt_bias sum every row's terms of both signs, in another order
        tol = 2e-2 if dtype == jnp.bfloat16 and name in ("q", "k", "a") else 1e-4
        assert close(g.astype(jnp.float32), w.astype(jnp.float32), tol, scale=largest), name


@pytest.mark.parametrize("sums", ["rolls", "triangle"])
@pytest.mark.parametrize("length,chunk,tile", OPERAND_LENGTHS[1:], ids=OPERAND_IDS[1:])
def test_the_running_sum_in_vmem_is_cumsum_to_float32_rounding(length, chunk, tile, sums, monkeypatch):
    """Either way of summing a chunk's rows (the module's ``_SUM_FORM``),
    forward and transposed, against ``jnp.cumsum`` of the gate itself; the
    rows past the sequence have ``g = 0`` and ``k = 0``."""
    monkeypatch.setattr(rule_ops, "_SUM_FORM", sums)
    jax.clear_caches()  # a module constant: nothing traced with the other may stay
    q, k, (a, a_log, dt_bias) = args = raw_operands(length, "random", seed=13)
    (_, kn, gamma, _, _), grads = operands_and_gradients(in_vmem_operands(tile), chunk, args)
    g = BOUND * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * (a + dt_bias.reshape(a.shape[2:])))
    want = jnp.cumsum(_by_chunk(g, chunk), axis=3)
    assert close(gamma, want, 1e-6)
    pad = -length % chunk
    # g = 0 there: the sum stands still (to rounding: the rolls add a row's terms in another order than its neighbour's)
    assert close(gamma[-1, :, :, chunk - pad:], gamma[-1, :, :, chunk - pad - 1:-1], 1e-6)
    assert not np.any(np.asarray(kn[-1, :, :, chunk - pad:])) and bool(np.all(np.asarray(kn[-1, :, :, 0])))
    _, want_grads = operands_and_gradients(xla_operands, chunk, args)
    largest = max(float(jnp.max(jnp.abs(w))) for w in want_grads)
    assert all(close(g, w, 1e-4, scale=largest) for g, w in zip(grads, want_grads))
    jax.clear_caches()


@pytest.mark.parametrize("length", [128, 150], ids=["whole_chunks", "ragged"])
@pytest.mark.parametrize("gate", sorted(GATES))
def test_the_rule_from_a_blocks_arrays_is_the_rule_a_token_at_a_time(gate, length, monkeypatch):
    """Through the new entry, on this backend (XLA's programs) and as a TPU
    would run it (``rule_form`` told so: both pairs of kernels in the
    interpreter): outputs, state and the least ``g``, against the rule a
    token at a time on operands normalised and gated outside."""
    q, k, (a, a_log, dt_bias) = raw_operands(length, gate, seed=14, batch=1)
    v = jax.random.normal(jax.random.PRNGKey(15), (1, length, 2, DV))
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(16), (1, length, 2)))
    g = BOUND * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * (a + dt_bias.reshape(a.shape[2:])))
    want, want_state = jax.jit(gated_delta_rule_recurrent)(l2_normalise(q) * 128 ** -0.5, l2_normalise(k), v, g, beta)
    from_raw = lambda: jax.jit(  # a trace a call: the form is picked in it
        lambda *args: gated_delta_rule_from_raw(*args, a_log=a_log, dt_bias=dt_bias, lower_bound=BOUND)
    )(q, k, v, a, beta)
    for on_tpu in (False, True):
        monkeypatch.setattr(attention_ops, "_on_tpu", lambda: on_tpu)
        out, state, least = from_raw()
        assert close(out, want) and close(state, want_state) and close(least, jnp.min(g), 1e-6, scale=abs(BOUND))


def test_the_dispatch_log_says_who_computed_the_operands(monkeypatch):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, 448, 2, 128), jnp.bfloat16), ((1, 448, 2, 128), jnp.bfloat16), ((1, 448, 2, DV), jnp.bfloat16),
        ((1, 448, 2, 128), jnp.bfloat16), ((1, 448, 2), jnp.float32),
    )]
    rest = dict(a_log=jnp.zeros((2,)), dt_bias=jnp.zeros((256,)), lower_bound=BOUND)
    attention_ops.clear_dispatch_log()
    jax.eval_shape(lambda *a: gated_delta_rule_from_raw(*a, **rest), *args)
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    jax.eval_shape(lambda *a: gated_delta_rule_from_raw(*a, **rest), *args)  # seven chunks: they do not pair up
    log = [r for r in attention_ops.snapshot_dispatch_log() if r["op"] == "gated_delta_rule"]
    attention_ops.clear_dispatch_log()
    common = {"op": "gated_delta_rule", "shape": [1, 448, 2, 128], "value_heads": 2, "chunk": 64, "dtype": "bfloat16",
              **VECTOR, "rule": "xla"}
    assert log == [
        {**common, "refused": "non-TPU backend", "operands": "xla", "operands_refused": "non-TPU backend"},
        {**common, "refused": "7 chunks do not pair up", "operands": "kernel", "operands_tile": 7},
    ]
