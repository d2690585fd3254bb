"""Fused short-sequence attention kernel vs the XLA reference — tier-1
interpret-mode numerics across the model-zoo shape table (ISSUE 6
acceptance: fwd + grads within bf16 tolerance incl. the bias path).

Shapes stay at small B·H so the interpret-mode kernels keep tier-1 fast;
the sequence-length geometry (197, 785, ragged, class-attention) is the
thing under test, not the batch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sav_tpu.ops.attention import xla_attention
from sav_tpu.ops.fused_attention import (
    FUSED_VMEM_BUDGET,
    fused_attention,
    fused_eligible,
    fused_vmem_bytes,
)


def _qkv(b=2, lq=197, lk=None, h=2, d=64, dtype=jnp.float32, seed=0):
    lk = lk or lq
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, lq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, lk, h, d), dtype)
    v = jax.random.normal(ks[2], (b, lk, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "b,lq,lk,h,d",
    [
        (2, 197, 197, 2, 64),  # DeiT/ViT-S @ 224 — the flagship shape
        (2, 197, 197, 4, 48),  # CaiT-XXS trunk geometry (H=4, D=48)
        (1, 785, 785, 1, 32),  # TNT outer: multi-q-block via padding
        (2, 50, 50, 2, 32),  # ragged: padded q rows AND kv cols
        (2, 1, 197, 2, 64),  # class attention: single query row
        (2, 196, 49, 2, 64),  # CvT: downsampled K/V
        (2, 197, 197, 6, 64),  # DeiT-S whole: six heads of one [L, 384] block
        (2, 197, 197, 12, 64),  # ViT-B whole: twelve heads, [L, 768]
        (3, 50, 50, 3, 40),  # ragged: odd batch, heads that straddle lane tiles
    ],
)
def test_fused_matches_xla_fwd_and_grads(b, lq, lk, h, d):
    q, k, v = _qkv(b=b, lq=lq, lk=lk, h=h, d=d)
    ref = xla_attention(q, k, v)
    out = fused_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )

    def loss_f(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    gf = jax.grad(loss_f(fused_attention), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_f(xla_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-4, rtol=5e-4
        )


@pytest.mark.parametrize(
    "bias_shape",
    [
        (2, 4, 50, 50),  # full per-(B,H)
        (1, 1, 50, 50),  # fully shared ('single' mode, any block_b)
        (1, 4, 50, 50),  # head-shared ('per_head' modular indexing)
        (2, 1, 50, 50),  # batch-shared ('per_batch' single-row blocks)
    ],
)
def test_fused_bias_matches_xla_fwd_and_grads(bias_shape):
    """Every bias broadcast pattern: forward rides the fused kernel
    (compact biases stay compact — no [B,H,L,L] materialization); the
    bias gradient runs the shared dense recompute."""
    q, k, v = _qkv(b=2, lq=50, lk=50, h=4, d=32)
    bias = jax.random.normal(jax.random.PRNGKey(9), bias_shape)
    ref = xla_attention(q, k, v, bias)
    out = fused_attention(q, k, v, bias)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )

    def loss_f(fn):
        return lambda q, k, v, b: jnp.sum(jnp.square(fn(q, k, v, b)))

    gf = jax.grad(loss_f(fused_attention), argnums=(0, 1, 2, 3))(q, k, v, bias)
    gx = jax.grad(loss_f(xla_attention), argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b_ in zip(gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-4, rtol=5e-4
        )


def test_fused_multi_q_block_accumulation():
    """block_q < q_len drives the backward's dk/dv accumulation across
    sequential q-block grid cells (the kv single-block makes dq direct)."""
    q, k, v = _qkv(b=1, lq=320, lk=256, h=2, d=40)

    def loss_f(fn, **kw):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v, **kw)))

    gf = jax.grad(
        loss_f(fused_attention, block_q=128), argnums=(0, 1, 2)
    )(q, k, v)
    gx = jax.grad(loss_f(xla_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-4, rtol=5e-4
        )


def test_fused_explicit_block_b():
    q, k, v = _qkv(b=2, lq=64, lk=64, h=2, d=32)
    ref = xla_attention(q, k, v)
    for bb in (1, 2, 4):  # 4 does not divide B*H=4? it does; 8 would not
        out = fused_attention(q, k, v, block_b=bb)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )
    # A block_b that does not divide B*H falls back to 1 instead of dying.
    out = fused_attention(q, k, v, block_b=3)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_fused_bf16():
    q, k, v = _qkv(lq=197, d=64, dtype=jnp.bfloat16)
    ref = xla_attention(q, k, v)
    out = fused_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_fused_bf16_grads_finite_and_close():
    q, k, v = _qkv(lq=197, d=64, dtype=jnp.bfloat16)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v).astype(jnp.float32)))

    gf = jax.grad(lambda *a: loss(fused_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    gx = jax.grad(lambda *a: loss(xla_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    for a, b_ in zip(gf, gx):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b_, atol=0.15, rtol=0.15)


@pytest.mark.parametrize(
    "b,lq,lk,h,d,block_b",
    [
        (2, 197, 197, 6, 64, None),  # DeiT-S
        (2, 197, 197, 12, 64, 2),  # ViT-B, two batch elements a grid cell
        (3, 50, 50, 3, 40, None),  # ragged
    ],
)
def test_fused_bf16_matches_f32_reference_fwd_and_grads(b, lq, lk, h, d, block_b):
    """bf16 in, f32 softmax in VMEM: forward and all three gradients stay
    as close to the float32 reference as the dense bf16 path does (the
    kernel rounds the probabilities once, the dense path the logits too)."""
    q, k, v = _qkv(b=b, lq=lq, lk=lk, h=h, d=d, dtype=jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.bfloat16)

    def fwd_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args[:3])
        return (out,) + vjp(args[3].astype(out.dtype))

    ref = fwd_and_grads(xla_attention, *(x.astype(jnp.float32) for x in (q, k, v, g)))
    dense = fwd_and_grads(
        lambda q, k, v: xla_attention(q, k, v, logits_dtype=jnp.bfloat16), q, k, v, g
    )
    fused = fwd_and_grads(
        lambda q, k, v: fused_attention(q, k, v, block_b=block_b), q, k, v, g
    )

    def gap(x, r):
        x, r = np.asarray(x, np.float32), np.asarray(r, np.float32)
        return np.linalg.norm(x - r) / np.linalg.norm(r)

    for f, x, r in zip(fused, dense, ref):
        assert f.dtype == jnp.bfloat16 and np.isfinite(np.asarray(f, np.float32)).all()
        assert gap(f, r) < 8e-3
        assert gap(f, r) < 1.25 * gap(x, r)


@pytest.mark.parametrize(
    "b,lq,lk,h,d,block_q",
    [
        (2, 197, 197, 6, 64, 256),  # one q block of the sequence's own length
        (1, 785, 785, 2, 32, 256),  # four q blocks, the last one 17 rows
        (2, 196, 49, 2, 64, 128),  # Lq != Lkv, two q blocks
    ],
)
def test_fused_residual_is_the_compact_logsumexp(b, lq, lk, h, d, block_q):
    """What the forward saves for the backward: ``[B, H, Lq]`` float32,
    the rows' logsumexp and nothing lane-broadcast."""
    from sav_tpu.ops.fused_attention import _fused_forward

    q, k, v = _qkv(b=b, lq=lq, lk=lk, h=h, d=d)
    scale = d**-0.5
    out, lse = _fused_forward(q, k, v, None, scale, block_q, None, None, with_lse=True)
    assert lse.shape == (b, h, lq) and lse.dtype == jnp.float32
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(logits, axis=-1)),
        atol=1e-4, rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xla_attention(q, k, v)), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_wrapper_moves_no_tensor_outside_the_kernel(dtype):
    """Forward and backward are each ONE ``pallas_call`` between free
    reshapes: no pad of L or D, no transpose to ``[B·H, L, D]``, no
    broadcast residual, and every kernel operand is the model's own
    ``[B, L, H·D]`` (or the ``[B, H, L]`` logsumexp)."""
    q, k, v = _qkv(b=2, lq=197, h=6, d=64, dtype=dtype)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fused_attention(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    def flat(jaxpr):
        """Every equation, those of nested calls (custom_vjp, pjit)
        included; a kernel's own body is not the wrapper's."""
        for e in jaxpr.eqns:
            nested = [] if e.primitive.name == "pallas_call" else [
                getattr(v, "jaxpr", v) for v in e.params.values()
                if hasattr(getattr(v, "jaxpr", v), "eqns")
            ]
            if nested:
                for sub in nested:
                    yield from flat(sub)
            else:
                yield e

    eqns = list(flat(jax.make_jaxpr(fwd_bwd)(q, k, v).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    moved = {e.primitive.name for e in eqns} & {
        "pad", "transpose", "broadcast_in_dim", "concatenate", "dynamic_slice", "slice", "gather",
    }
    # The loss's own broadcast of its cotangent is the test's, not the wrapper's.
    assert moved <= {"broadcast_in_dim"}
    for call in calls:
        for var in list(call.invars) + list(call.outvars):
            assert var.aval.shape in ((2, 197, 384), (2, 6, 197)), var.aval


def test_fused_softmax_stability():
    """Large logit magnitudes: the single-pass softmax still subtracts the
    row max (it has the whole row), so ±100-scale logits stay finite."""
    q, k, v = _qkv(lq=64, lk=64, d=32)
    out = fused_attention(100.0 * q, 100.0 * k, v)
    assert np.isfinite(np.asarray(out)).all()


def test_fused_rejects_over_budget_kv():
    """The single-KV-block VMEM budget is a hard precondition."""
    long = 4096
    assert not fused_eligible(long, long, 64)
    q, k, v = _qkv(b=1, lq=8, lk=long, h=1, d=64)
    with pytest.raises(ValueError, match="VMEM budget"):
        fused_attention(q, k, v)


def test_fused_rejects_non_4d():
    x = jnp.zeros((4, 8, 8))
    with pytest.raises(ValueError, match=r"\[B, L, H, D\]"):
        fused_attention(x, x, x)
    q = jnp.zeros((2, 8, 2, 8))
    with pytest.raises(ValueError, match="bias must be 4-D"):
        fused_attention(q, q, q, jnp.zeros((8, 8)))


def test_fused_shared_bias_modes_with_explicit_block_b():
    """The modular bias index maps under every legal block_b, plus the
    constraint fallback (a block_b that would straddle a batch boundary
    for a head-ful shared bias drops to 1, never mis-indexes)."""
    q, k, v = _qkv(b=2, lq=33, lk=33, h=4, d=16)
    for bias_shape in ((1, 4, 33, 33), (2, 1, 33, 33), (1, 1, 33, 33)):
        bias = jax.random.normal(jax.random.PRNGKey(3), bias_shape)
        ref = xla_attention(q, k, v, bias)
        for bb in (1, 2, 4, 8):  # 8 > heads: constrained modes fall back
            out = fused_attention(q, k, v, bias, block_b=bb)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
                err_msg=f"bias_shape={bias_shape} block_b={bb}",
            )


def test_vmem_estimate_monotonic_and_pinned():
    """The eligibility frontier the dispatcher's short band keys on:
    model-zoo lengths are inside the budget, 2k+ tokens are not, and the
    estimate grows monotonically in every dimension."""
    assert fused_eligible(197, 197, 64)
    assert fused_eligible(197, 197, 48)
    assert fused_eligible(785, 785, 64)
    assert fused_eligible(1, 197, 64)  # class attention
    assert not fused_eligible(2048, 2048, 64)
    assert not fused_eligible(4096, 4096, 64)
    base = fused_vmem_bytes(197, 197, 64)
    assert base <= FUSED_VMEM_BUDGET
    assert fused_vmem_bytes(197, 394, 64) > base
    assert fused_vmem_bytes(394, 197, 64) >= base
    assert fused_vmem_bytes(197, 197, 256) > base  # dim pads to 128 lanes
    assert fused_vmem_bytes(197, 197, 64, block_b=8) > fused_vmem_bytes(
        197, 197, 64, block_b=1
    )
