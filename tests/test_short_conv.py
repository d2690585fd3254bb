"""The double-gated short convolution (``sav_tpu/models/layers/short_conv.py``)
and the fused core it runs (``layers/causal_conv.py::gated_causal_conv``)
against three shifted products written out in float32, on the CPU.

Tolerances. In float32 the block and the written-out form differ in the order
of three-term sums only: 1e-6 of the compared tensor's largest entry. In
bfloat16 the program rounds ``B * x~`` and its result once each: 2e-2.

``form = "kernel"`` is the core as the Pallas calls of
``sav_tpu/ops/causal_conv.py`` in the interpreter, called past ``conv_form``
(which says ``xla`` on this CPU), at blocks of 16 rows: three a sequence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sav_tpu.models.layers import causal_conv
from sav_tpu.models.layers.causal_conv import causal_depthwise_conv, gated_causal_conv
from sav_tpu.models.layers.short_conv import ShortConvBlock

BATCH, SEQ, DIM = 2, 24, 16


def close(got, want, tol=1e-6):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) <= tol * float(np.max(np.abs(want)))


FORMS = {"xla": (SEQ, DIM), "kernel": (48, 128)}  # the sequence and the channels each is tried at


def core(form):
    if form == "xla":
        return gated_causal_conv
    return lambda b, c, x, kernel: causal_conv._gated_conv_in_vmem(
        jnp.concatenate([b, c, x], axis=-1), kernel, 16, 128, True)


def operands(seed=0, width=3, dtype=jnp.float32, form="xla"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    seq, dim = FORMS[form]
    b, c, x, g = (jax.random.normal(k, (BATCH, seq, dim)).astype(dtype) for k in ks[:4])
    return b, c, x, jax.random.normal(ks[4], (width, dim)) * width ** -0.5, g


def shifted_products(b, c, x, kernel):
    """``c_t = sum_j w_j u_{t - (W - 1) + j}`` with ``u = b * x`` as one
    shifted product a tap, zeros before the sequence starts; then ``C * c``."""
    u, width = b * x, kernel.shape[0]
    conv = jnp.zeros_like(u)
    for j in range(width):
        back = width - 1 - j  # tap j reads u_{t - back}
        shifted = jnp.concatenate([jnp.zeros_like(u[:, :back]), u[:, :u.shape[1] - back]], axis=1)
        conv = conv + kernel[j] * shifted
    return c * conv


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("width", [3, 4])
def test_the_core_is_three_shifted_products_between_two_gates(width, form):
    b, c, x, kernel, _ = operands(width=width, form=form)
    assert close(core(form)(b, c, x, kernel), shifted_products(b, c, x, kernel))
    # The convolution alone, as the delta-rule block reads it from the same home.
    assert close(causal_depthwise_conv(b * x, kernel), shifted_products(b, jnp.ones_like(c), x, kernel))


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("width", [3, 4])
def test_the_cores_own_backward_is_jaxs_derivative_of_the_written_out_form(width, form):
    b, c, x, kernel, g = operands(seed=1, width=width, form=form)
    out, pull = jax.vjp(core(form), b, c, x, kernel)
    want, want_pull = jax.vjp(shifted_products, b, c, x, kernel)
    assert close(out, want)
    for name, got, ref in zip("db dc dx dkernel".split(), pull(g), want_pull(g)):
        assert got.shape == ref.shape and got.dtype == ref.dtype and close(got, ref, 2e-6), name


@pytest.mark.parametrize("form", list(FORMS))
def test_the_core_in_bfloat16_keeps_its_operands_dtype(form):
    b, c, x, kernel, g = operands(seed=2, dtype=jnp.bfloat16, form=form)
    out, pull = jax.vjp(core(form), b, c, x, kernel)
    wide = [t.astype(jnp.float32) for t in (b, c, x)]
    assert out.dtype == jnp.bfloat16 and close(out, shifted_products(*wide, kernel), 2e-2)
    grads = pull(g)
    assert [t.dtype for t in grads] == [jnp.bfloat16] * 3 + [jnp.float32]
    for got, ref in zip(grads, jax.vjp(shifted_products, *wide, kernel)[1](g.astype(jnp.float32))):
        assert close(got, ref, 3e-2)


@pytest.fixture(scope="module")
def block_and_params():
    block = ShortConvBlock(conv_width=3)
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, SEQ, DIM))
    params = block.init(jax.random.PRNGKey(4), x)["params"]
    return block, params, x


def written_out_block(params, x):
    gates = x @ params["to_qkv"]["in_proj"]["kernel"]
    b, c, inner = gates[..., :DIM], gates[..., DIM:2 * DIM], gates[..., 2 * DIM:]
    return shifted_products(b, c, inner, params["conv"]["kernel"]) @ params["to_out"]["out_proj"]["kernel"]


def test_the_block_against_the_written_out_form_and_its_gradient(block_and_params):
    block, params, x = block_and_params
    assert jax.tree.map(lambda leaf: leaf.shape, params) == {
        "to_qkv": {"in_proj": {"kernel": (DIM, 3 * DIM)}}, "conv": {"kernel": (3, DIM)},
        "to_out": {"out_proj": {"kernel": (DIM, DIM)}},
    }  # no bias anywhere
    with jax.default_matmul_precision("highest"):
        (out, stats), pull = jax.vjp(lambda p, x: block.apply({"params": p}, x), params, x)
        want, want_pull = jax.vjp(written_out_block, params, x)
        g = jax.random.normal(jax.random.PRNGKey(5), out.shape)
        got_grads = pull((g, jax.tree.map(jnp.zeros_like, stats)))
        want_grads = want_pull(g)
    assert close(out, want, 2e-6)
    for got, ref in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        assert close(got, ref, 5e-6)
    # The one stat: the largest RMS of a sequence's C * c, no gradient.
    gates = x @ params["to_qkv"]["in_proj"]["kernel"]
    mixed = shifted_products(gates[..., :DIM], gates[..., DIM:2 * DIM], gates[..., 2 * DIM:], params["conv"]["kernel"])
    assert list(stats) == ["out_rms_max"]
    assert float(stats["out_rms_max"]) == pytest.approx(
        float(jnp.sqrt(jnp.max(jnp.mean(jnp.square(mixed), axis=(1, 2))))), rel=1e-5)


@pytest.mark.parametrize("position", [0, 5, SEQ - 1])
def test_a_token_reads_nothing_after_itself(block_and_params, position):
    """Token t's output is unchanged by tokens after t, to the bit; and a
    change at t reaches t .. t + 2 only (width 3)."""
    block, params, x = block_and_params
    apply = jax.jit(lambda x: block.apply({"params": params}, x)[0])
    base = apply(x)
    moved = apply(x.at[:, position].add(1.0))
    changed = np.any(np.asarray(base != moved), axis=(0, 2))
    assert not changed[:position].any()
    assert changed[position] and not changed[position + 3:].any()
