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
times ``TIGHT``."""

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
    CHUNK, SUB_BLOCK, gated_delta_rule, gated_delta_rule_recurrent, rule_form,
)

TIGHT = 2e-5
BOUND = -5.0  # the public config's kda_lower_bound
BATCH, HEADS, DK, DV = 2, 3, 32, 16


def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def operands(length, gate, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = l2(jax.random.normal(ks[0], (BATCH, length, HEADS, DK))) * DK ** -0.5
    k = l2(jax.random.normal(ks[1], (BATCH, length, HEADS, DK)))
    v = jax.random.normal(ks[2], (BATCH, length, HEADS, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (BATCH, length, HEADS)))
    g = {
        "bound": jnp.full((BATCH, length, HEADS, DK), BOUND),  # every channel and token at the gate's lower bound
        "zero": jnp.zeros((BATCH, length, HEADS, DK)),  # no decay at all: the plain delta rule
        "random": BOUND * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (BATCH, length, HEADS, DK))),
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


def test_the_forms_record_says_what_refused_the_kernels():
    form = rule_form(64, 64, 128, 1, by_lane=True, on_tpu=True)
    assert form["rule"] == "xla" and form["decay"] == "vector" and "lane" in form["refused"]
    assert rule_form(64, 64, 128, 2, on_tpu=True) == {"rule": "kernel", "chunk_tile": 8}  # the scalar form's, as it was
    attention_ops.clear_dispatch_log()
    jax.eval_shape(gated_delta_rule, *operands(192, "zero"))  # a length no other test traces: a cached trace logs nothing
    records = [r for r in attention_ops.snapshot_dispatch_log() if r["op"] == "gated_delta_rule"]
    assert len(records) == 1 and records[0]["decay"] == "vector" and records[0]["rule"] == "xla"
    assert records[0]["shape"] == [BATCH, 192, HEADS, DK] and records[0]["value_heads"] == HEADS
    q, k, v, g, beta = operands(192, "zero")
    jax.eval_shape(gated_delta_rule, q, k, v, g[..., 0], beta)
    records = [r for r in attention_ops.snapshot_dispatch_log() if r["op"] == "gated_delta_rule"]
    assert len(records) == 2 and "decay" not in records[1]  # the scalar form's record is the one it had


def test_a_vector_decay_takes_one_key_head_a_value_head():
    q, k, v, g, beta = operands(64, "zero")
    with pytest.raises(ValueError, match="gated delta rule"):
        gated_delta_rule(q[:, :, :1], k[:, :, :1], v, g, beta)  # grouped heads
    with pytest.raises(ValueError, match="gated delta rule"):
        gated_delta_rule(q, k, v, g[..., :8], beta)  # lanes that are not the key's
