"""The gated delta rule (``sav_tpu/ops/gated_delta.py``) and the block around
it (``sav_tpu/models/layers/gated_delta.py``) at toy sizes on the CPU.

Tolerances. The chunked form and the per-token recurrence both compute in
float32 here, in different orders (a triangular system and matrix products a
chunk against one rank-one update a token), so ``TIGHT`` = 2e-5 of the
compared tensor's largest entry; a gradient with respect to ``g`` under decays
near 0 is itself near 0 everywhere and is held to 1e-3 of its largest entry
(it reads 1.5e-4: a sum of terms of both signs, each exp(-20) of an
activation).

The kernels of the state-free part run here in the Pallas interpreter, called
past the rule that picks the path (``rule_form`` says ``xla`` on a CPU and for
these toy heads), and are held to the same tolerances, against the recurrence
and against XLA's form. So do the convolution's kernels
(``sav_tpu/ops/causal_conv.py``): ``form = "kernel"`` below is the Pallas call
of each direction in the interpreter at blocks of 16 or 32 rows, so that a
sequence is several blocks and a tap reads across their edges."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sav_tpu.models.layers import causal_conv as conv_forms
from sav_tpu.models.layers.gated_delta import (
    GatedDeltaNetBlock,
    causal_conv_silu,
    causal_depthwise_conv,
    l2_normalise,
    split_by_key_head,
)
from sav_tpu.ops import attention
from sav_tpu.ops.causal_conv import conv_form
from sav_tpu.ops.gated_delta import (
    _by_chunk,
    _chunked,
    _operands,
    _operands_in_vmem,
    _prepare,
    _prepare_in_vmem,
    _summed_by_chunk,
    _unit_lower_inverse,
    _wide_inverse,
    gated_delta_rule,
    gated_delta_rule_from_raw,
    gated_delta_rule_recurrent,
    rule_form,
)

TIGHT = 2e-5
# Jitted: one compile a shape, where op-by-op dispatch compiles every small
# operation apart.
chunked = jax.jit(gated_delta_rule, static_argnames="chunk")
recurrent = jax.jit(gated_delta_rule_recurrent)
DECAYS = {"near_0": (-20.0, -5.0), "near_1": (-1e-3, -1e-5), "mixed": (-3.0, -0.01)}


def in_vmem(tile):
    """The chunked rule with its state-free part in the kernels, interpreted,
    ``tile`` chunks a grid step."""
    prepare = functools.partial(_prepare_in_vmem, tile=tile, interpret=True)

    def rule(q, k, v, g, beta, chunk):
        operands = (_by_chunk(q, chunk), _by_chunk(k, chunk), _summed_by_chunk(g, chunk))
        return _chunked(prepare, operands, operands, v, beta)

    return jax.jit(rule, static_argnames="chunk")


def close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) <= tol * max(float(np.max(np.abs(want))), 1e-30)


def operands(length, key_heads, heads, decay, dk=16, dv=16, batch=2, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    # Keys with a common component, as a SiLU leaves them: k_i . k_j is far from 0.
    q = l2_normalise(jax.random.normal(ks[0], (batch, length, key_heads, dk))) * dk ** -0.5
    k = l2_normalise(jax.random.normal(ks[1], (batch, length, key_heads, dk)) + 0.5)
    v = jax.random.normal(ks[2], (batch, length, heads, dv))
    g = jax.random.uniform(ks[3], (batch, length, heads), minval=DECAYS[decay][0], maxval=DECAYS[decay][1])
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, length, heads)) + 2.0)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length,chunk", [(150, 64), (37, 16), (5, 64)])
@pytest.mark.parametrize("key_heads,heads", [(2, 4), (3, 3)])
def test_the_chunked_rule_is_the_recurrence(length, chunk, key_heads, heads, decay):
    args = operands(length, key_heads, heads, decay)
    out, state = chunked(*args, chunk=chunk)
    want, want_state = recurrent(*args)
    assert out.shape == want.shape == args[2].shape
    assert close(out, want) and close(state, want_state)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length,chunk", [(150, 64), (37, 16)])
def test_the_chunked_rules_gradients_are_the_recurrences(length, chunk, decay):
    args = operands(length, 2, 4, decay)

    def scalar(rule):
        def f(*a):
            out, state = rule(*a)
            return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.square(state))
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))

    got = scalar(functools.partial(gated_delta_rule, chunk=chunk))(*args)
    want = scalar(gated_delta_rule_recurrent)(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert close(a, b, 1e-3 if (name, decay) == ("g", "near_0") else TIGHT), name


def test_padding_rows_leave_the_state_alone():
    """A length that is no whole number of chunks ends in the state the
    recurrence ends in, and the same sequence cut at a chunk's edge and
    continued gives the same outputs as the whole."""
    q, k, v, g, beta = operands(70, 2, 4, "mixed")
    whole, state = chunked(q, k, v, g, beta, chunk=64)  # 58 padded rows
    _, want = recurrent(q, k, v, g, beta)
    assert close(state, want)
    short, _ = chunked(q[:, :64], k[:, :64], v[:, :64], g[:, :64], beta[:, :64], chunk=64)
    assert close(whole[:, :64], short)  # causal: later tokens do not reach back


@pytest.mark.parametrize("n", [8, 16, 24, 64])
def test_the_triangular_inverse_is_exact_where_the_series_is_not(n):
    """Entries near 1 throughout: ``sum (-A)^k`` loses every digit in float32
    (its terms reach 1e17 at n 64), the doubling does not; 24 is no power of two."""
    lower = jnp.tril(0.9 + 0.1 * jax.random.uniform(jax.random.PRNGKey(n), (3, n, n)), -1)
    got = np.asarray(jax.jit(_unit_lower_inverse)(lower), np.float64)
    system = np.eye(n) + np.asarray(lower, np.float64)
    assert np.max(np.abs(got @ system - np.eye(n))) < 1e-4
    assert np.allclose(got, np.linalg.inv(system), atol=1e-4 * np.max(np.abs(np.linalg.inv(system))))
    assert np.array_equal(np.triu(got, 1), np.zeros_like(got))


def test_bfloat16_operands_stay_near_the_float32_rule():
    """The compute dtype's operands with float32 sums and a float32 state: the
    rounding of q, k, v, T and W, a few parts in a thousand of the output."""
    args = operands(150, 2, 4, "mixed", dtype=jnp.bfloat16)
    out, state = chunked(*args)
    want, want_state = recurrent(*args)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert close(out.astype(jnp.float32), want, 3e-2) and close(state, want_state, 3e-2)


# (length, chunk, chunks a grid step): a padded last chunk in each; the second
# takes two grid steps along the chunks.
KERNEL_LENGTHS = [(150, 64, 3), (90, 16, 3)]


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length,chunk,tile", KERNEL_LENGTHS)
@pytest.mark.parametrize("key_heads,heads", [(2, 4), (3, 3)])
def test_the_kernels_rule_is_the_recurrence_and_xlas_form(length, chunk, tile, key_heads, heads, decay):
    args = operands(length, key_heads, heads, decay)
    out, state = in_vmem(tile)(*args, chunk=chunk)
    want, want_state = recurrent(*args)
    assert out.shape == want.shape and close(out, want) and close(state, want_state)
    xla, xla_state = chunked(*args, chunk=chunk)
    assert close(out, xla, 2e-6) and close(state, xla_state, 2e-6)


@pytest.mark.parametrize(
    "length,chunk,tile,key_heads,heads,decay",
    [(150, 64, 3, 2, 4, decay) for decay in sorted(DECAYS)] + [(37, 16, 3, 2, 4, decay) for decay in sorted(DECAYS)]
    + [(37, 16, 1, 3, 3, "mixed"), (37, 16, 1, 3, 3, "near_1")],
)
def test_the_kernels_gradients_are_the_recurrences(length, chunk, tile, key_heads, heads, decay):
    """All five, through the backward kernel (dq, dk, dg, dbeta) and the scan's
    transpose (dv, and the cotangents the kernel is handed). With one value
    head a key head and decays near 0 the gradient with respect to ``g`` is
    1e-3 of an activation and either program reads 2e-3 to 3e-3 of it off the
    recurrence: not a case."""
    args = operands(length, key_heads, heads, decay)

    def scalar(rule):
        def f(*a):
            out, state = rule(*a)
            return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.square(state))
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))

    got = scalar(functools.partial(in_vmem(tile), chunk=chunk))(*args)
    want = scalar(gated_delta_rule_recurrent)(*args)
    xla = scalar(functools.partial(gated_delta_rule, chunk=chunk))(*args)
    for name, a, b, c in zip("q k v g beta".split(), got, want, xla):
        # Under decays near 0 each program is within 1e-3 of the recurrence (they
        # read 5e-4 to 9e-4), so the two are within 2e-3 of each other.
        near, apart = (1e-3, 2e-3) if (name, decay) == ("g", "near_0") else (TIGHT, TIGHT)
        assert close(a, b, near) and close(a, c, apart), name


def test_the_kernels_results_are_xlas_on_bfloat16_operands():
    """The state-free part alone, where the two programs round alike: ``T
    beta`` and the masked ``Q K^T`` to a bfloat16 unit in the last place, the
    four gradients to the rounding of the bfloat16 cotangents' products."""
    q, k, _, g, beta = operands(128, 2, 4, "mixed", dtype=jnp.bfloat16)
    q, k, beta = (_by_chunk(x, 64) for x in (q, k, beta))
    gamma = _summed_by_chunk(g, 64)
    weights = jax.random.normal(jax.random.PRNGKey(7), (2, 2, 2, 4, 64, 64))

    def scalar(prepare):
        def f(*a):
            solved, inside = prepare(*a, 2)
            assert solved.dtype == inside.dtype == jnp.bfloat16
            return jnp.sum(weights[0] * solved) + jnp.sum(weights[1] * inside), (solved, inside)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True))

    (_, got), got_grads = scalar(functools.partial(_prepare_in_vmem, tile=2, interpret=True))(q, k, gamma, beta)
    (_, want), want_grads = scalar(_prepare)(q, k, gamma, beta)
    for a, b in zip(got, want):
        assert close(a.astype(jnp.float32), b.astype(jnp.float32), 2 ** -7)
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == b.dtype and close(a.astype(jnp.float32), b.astype(jnp.float32), 2e-2)


def test_bfloat16_operands_through_the_kernels_stay_near_the_float32_rule():
    args = operands(150, 2, 4, "mixed", dtype=jnp.bfloat16)
    out, state = in_vmem(3)(*args, chunk=64)
    want, want_state = recurrent(*args)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert close(out.astype(jnp.float32), want, 3e-2) and close(state, want_state, 3e-2)


@pytest.mark.parametrize("n,group", [(16, 1), (16, 2), (64, 2), (32, 4)])
def test_the_kernels_inverse_is_exact_where_the_series_is_not(n, group):
    """The same systems as above, a key head's ``group`` side by side as the
    kernels hold them."""
    lower = jnp.tril(0.9 + 0.1 * jax.random.uniform(jax.random.PRNGKey(n), (group, n, n)), -1)
    wide = jnp.concatenate(list(lower), axis=1)
    got = np.asarray(jax.jit(functools.partial(_wide_inverse, chunk=n, group=group))(wide), np.float64)
    for h in range(group):
        system = np.eye(n) + np.asarray(lower[h], np.float64)
        mine = got[:, h * n:(h + 1) * n]
        assert np.max(np.abs(mine @ system - np.eye(n))) < 1e-4
        assert np.allclose(mine, np.linalg.inv(system), atol=1e-4 * np.max(np.abs(np.linalg.inv(system))))
        assert np.array_equal(np.triu(mine, 1), np.zeros_like(mine))


def test_an_ill_conditioned_chunk_goes_through_the_forward_kernel():
    """Keys that all but coincide, ``beta`` 1 and no decay: the system's
    entries are ``k_i . k_j`` in 0.9 to 1, and ``T beta`` is its inverse."""
    chunk, dk = 64, 32
    k = l2_normalise(1.0 + 0.25 * jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, chunk, dk)))
    gamma, beta = jnp.zeros((1, 1, 2, chunk)), jnp.ones((1, 1, 2, chunk))
    solved, inside = _prepare_in_vmem(k, k, gamma, beta, 2, tile=1, interpret=True)
    pairs = np.asarray(jnp.einsum("id,jd->ij", k[0, 0, 0], k[0, 0, 0]), np.float64)
    assert np.min(np.tril(pairs, -1) + np.triu(np.ones_like(pairs))) > 0.85
    want = np.linalg.inv(np.eye(chunk) + np.tril(pairs, -1))
    assert np.abs(np.linalg.matrix_power(np.tril(pairs, -1), 32)).max() > 1e12  # a term of the series
    for h in range(2):
        assert np.allclose(np.asarray(solved[0, 0, h], np.float64), want, atol=1e-4 * np.abs(want).max())
        assert np.allclose(np.asarray(inside[0, 0, h]), np.tril(pairs), atol=1e-5)


def in_vmem_operands(tile):
    """The operands' kernels in the interpreter, ``tile`` chunks a grid step."""
    return lambda q, k, gate, chunk, bound=None: _operands_in_vmem(q, k, gate, chunk, bound, tile, True)


OPERANDS_IN_VMEM = {"operands": "kernel", "operands_tile": 16}
# (length, chunk, chunks a grid step): whole chunks in two grid steps; a padded
# last chunk in one step and in two; chunks of one 16-row tile.
OPERAND_LENGTHS = [(256, 64, 2), (150, 64, 3), (250, 64, 2), (90, 16, 2)]


def raw_operands(length, dtype=jnp.float32, seed=0, batch=2, key_heads=2, dk=128):
    """q and k as the convolution leaves them (a SiLU: a common component)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return tuple(jax.nn.silu(jax.random.normal(key, (batch, length, key_heads, dk)) + 0.5).astype(dtype) for key in ks)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length,chunk,tile", OPERAND_LENGTHS)
def test_the_operands_kernels_normalise_as_xlas_program(length, chunk, tile, dtype):
    """The scalar decay's operands: q and k normalised, rounded once to their
    dtype and chunk-major, zero past the sequence, and dq, dk through the
    normalisation's own derivative; the gate's part is absent (``gate`` None:
    ``g [B, L, H]`` is summed by XLA)."""
    q, k = raw_operands(length, dtype)
    weights = jax.random.normal(jax.random.PRNGKey(3), (2, -(-length // chunk), 2, 2, chunk, 128))

    def both(program):
        def f(q, k):
            (qn, kn, gamma), again, least = program(q, k, None, chunk)
            assert gamma is None and least is None and again[2] is None
            return jnp.sum(weights[0] * qn.astype(jnp.float32)) + jnp.sum(weights[1] * kn.astype(jnp.float32)), (qn, kn)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(q, k)

    (_, got), got_grads = both(in_vmem_operands(tile))
    (_, want), want_grads = both(lambda q, k, gate, chunk: _operands(q, k, gate, chunk, None))
    pad = -length % chunk
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape == weights.shape[1:]
        assert close(a.astype(jnp.float32), b.astype(jnp.float32), 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6)
        assert not pad or not np.any(np.asarray(a[-1, :, :, chunk - pad:].astype(jnp.float32)))
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == b.dtype == dtype and a.shape == q.shape
        assert close(a.astype(jnp.float32), b.astype(jnp.float32), 2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length", [128, 150])
def test_the_rule_from_a_blocks_arrays_is_the_recurrence(length, decay, monkeypatch):
    """Through the new entry, on this backend (XLA's programs) and as a TPU
    would run it (``rule_form`` told so: both pairs of kernels in the
    interpreter), with all five gradients: against the recurrence on
    operands normalised outside. dq and dk pass through the normalisation,
    which takes out a gradient's largest part (the one along the vector): what
    is left is held to 1e-4 of its largest entry (it reads 1.4e-5), and ``dg``
    under decays near 0 to 5e-3 (2.2e-3: the note at the top)."""
    _, _, v, g, beta = operands(length, 1, 2, decay, dv=32, batch=1)
    q, k = raw_operands(length, batch=1, key_heads=1)
    normalised = lambda rule: lambda q, k, *rest: rule(l2_normalise(q) * 128 ** -0.5, l2_normalise(k), *rest)

    def scalar(rule):
        def f(*a):
            out, state, *least = rule(*a)
            return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.square(state)), (out, state, least)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True))  # a trace a call: the form is picked in it

    (_, (want, want_state, _)), want_grads = scalar(normalised(gated_delta_rule_recurrent))(q, k, v, g, beta)
    for on_tpu in (False, True):
        monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
        (_, (out, state, (least,))), grads = scalar(gated_delta_rule_from_raw)(q, k, v, g, beta)
        assert close(out, want) and close(state, want_state) and float(least) == float(jnp.min(g))
        for name, a, b in zip("q k v g beta".split(), grads, want_grads):
            tol = 5e-3 if (name, decay) == ("g", "near_0") else 1e-4 if name in "qk" else TIGHT
            assert close(a, b, tol), (on_tpu, name)


RULE_FORMS = [
    # (chunks, chunk, d_k, value heads a key head, on a TPU) -> the state-free part's form, the operands'
    ((64, 64, 128, 2, True), {"rule": "kernel", "chunk_tile": 8}, OPERANDS_IN_VMEM),  # the hybrid decoder's cell
    ((3, 64, 128, 2, True), {"rule": "kernel", "chunk_tile": 3},
     {"operands": "kernel", "operands_tile": 3}),  # one tile holds every chunk
    ((16, 128, 256, 1, True), {"rule": "kernel", "chunk_tile": 8}, OPERANDS_IN_VMEM),
    ((64, 64, 128, 2, False), {"rule": "xla", "refused": "non-TPU backend"},
     {"operands": "xla", "operands_refused": "non-TPU backend"}),
    ((64, 64, 128, 1, True), {"rule": "xla", "refused": "1 value heads a key head x chunk 64 = 64 lanes"},
     OPERANDS_IN_VMEM),  # the operands' calls know no group
    ((64, 64, 128, 16, True), {"rule": "xla", "refused": "16 value heads a key head x chunk 64 = 1024 lanes"},
     OPERANDS_IN_VMEM),
    ((64, 64, 64, 2, True), {"rule": "xla", "refused": "key head 64 is not whole lane tiles"},
     {"operands": "xla", "operands_refused": "key head 64 is not whole lane tiles"}),
    ((64, 24, 128, 16, True), {"rule": "xla", "refused": "chunk 24 is not a power of two of whole 16-row tiles"},
     {"operands": "xla", "operands_refused": "chunk 24 is not whole 16-row tiles"}),
    ((64, 48, 128, 8, True), {"rule": "xla", "refused": "chunk 48 is not a power of two of whole 16-row tiles"},
     OPERANDS_IN_VMEM),  # three 16-row tiles: no inverse to double
    ((64, 48, 192, 8, True), {"rule": "xla", "refused": "chunk 48 is not a power of two of whole 16-row tiles"},
     {"operands": "xla", "operands_refused": "key head 192 is not whole lane tiles"}),
    ((12, 64, 128, 2, True), {"rule": "xla", "refused": "12 chunks are not whole tiles of 8"},
     {"operands": "kernel", "operands_tile": 12}),
    ((63, 64, 128, 2, True), {"rule": "xla", "refused": "63 chunks are not whole tiles of 8"},
     {"operands": "kernel", "operands_tile": 9}),  # the most chunks up to 16 that divide 63
]


@pytest.mark.parametrize("shape,form,operands_form", RULE_FORMS, ids=[str(shape) for shape, _, _ in RULE_FORMS])
def test_the_rule_picks_its_program_from_the_backend_and_the_shapes(shape, form, operands_form):
    *sizes, on_tpu = shape
    assert rule_form(*sizes, on_tpu=on_tpu) == {**form, **operands_form}


def test_the_dispatch_log_records_the_rules_form(monkeypatch):
    """One record a traced shape, beside the attention's: ``kernel`` with its
    tile where a TPU would run it (nothing runs here: the trace alone writes
    the record), ``xla`` with what refused on this backend."""
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((1, 192, 2, 128), jnp.bfloat16), ((1, 192, 2, 128), jnp.bfloat16), ((1, 192, 4, 128), jnp.bfloat16),
        ((1, 192, 4), jnp.float32), ((1, 192, 4), jnp.float32),
    )]
    attention.clear_dispatch_log()
    jax.eval_shape(lambda *a: gated_delta_rule(*a), *shapes)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    out, state = jax.eval_shape(lambda *a: gated_delta_rule(*a), *shapes)
    assert out.shape == (1, 192, 4, 128) and state.shape == (1, 4, 128, 128)
    log = attention.snapshot_dispatch_log()
    attention.clear_dispatch_log()
    common = {"op": "gated_delta_rule", "shape": [1, 192, 2, 128], "value_heads": 4, "chunk": 64, "dtype": "bfloat16"}
    assert log == [
        {**common, "rule": "xla", "refused": "non-TPU backend", "operands": "given"},
        {**common, "rule": "kernel", "chunk_tile": 3, "operands": "given"},
    ]
    jax.eval_shape(lambda *a: gated_delta_rule_from_raw(*a), *shapes)  # the same shapes from a block's own arrays
    monkeypatch.setattr(attention, "_on_tpu", lambda: False)
    jax.eval_shape(lambda *a: gated_delta_rule_from_raw(*a), *shapes)
    log = attention.snapshot_dispatch_log()
    attention.clear_dispatch_log()
    assert log == [
        {**common, "rule": "kernel", "chunk_tile": 3, "operands": "kernel", "operands_tile": 3},
        {**common, "rule": "xla", "refused": "non-TPU backend", "operands": "xla", "operands_refused": "non-TPU backend"},
    ]


def test_mismatched_heads_are_refused():
    q, k, v, g, beta = operands(16, 3, 4, "mixed")
    with pytest.raises(ValueError, match="gated delta rule"):
        gated_delta_rule(q, k, v, g, beta)


# ----------------------------------------------------------------- the block


def convolution(form, block_s=16):
    """The convolution alone as the form's program: XLA's, or the gated kernel
    between two gates of ones (``1 * conv(1 * x)``), interpreted."""
    if form == "xla":
        return causal_depthwise_conv
    ones = jnp.ones_like
    return lambda x, kernel: conv_forms._gated_conv_in_vmem(
        jnp.concatenate([ones(x), ones(x), x], axis=-1), kernel, block_s, 128, True)


def conv_silu(form, block_s=32):
    """``causal_conv_silu`` as the form's program (on this CPU ``conv_form``
    says ``xla``; the kernels are called past it, interpreted)."""
    if form == "xla":
        return causal_conv_silu
    return lambda x, kernel: conv_forms._conv_silu_in_vmem(x, kernel, block_s, 128, True)


@pytest.mark.parametrize("position", [0, 3, 14, 17])
@pytest.mark.parametrize("form,seq,channels", [("xla", 24, 6), ("kernel", 32, 128)])
def test_the_convolution_is_causal_and_reads_four_positions(form, seq, channels, position):
    conv = convolution(form)  # the kernel's blocks end at rows 15 and 31: position 14 reaches across
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, channels))
    kernel = jax.random.normal(jax.random.PRNGKey(2), (4, channels))
    base = conv(x, kernel)
    moved = conv(x.at[:, position].add(1.0), kernel)
    changed = np.flatnonzero(np.max(np.abs(np.asarray(moved - base)), axis=(0, 2)) > 0)
    assert list(changed) == [p for p in range(position, position + 4) if p < seq]
    # Written out: y_t = sum_i kernel[i] x_{t - 3 + i}, zeros before the start.
    for t in (5, 16, 17):
        want = sum(kernel[i] * x[:, t - 3 + i] for i in range(4))
        assert np.allclose(np.asarray(base[:, t]), np.asarray(want), atol=1e-6)
    assert np.allclose(np.asarray(base[:, 0]), np.asarray(kernel[3] * x[:, 0]), atol=1e-6)
    assert np.allclose(np.asarray(base[:, 1]), np.asarray(kernel[3] * x[:, 1] + kernel[2] * x[:, 0]), atol=1e-6)


@pytest.mark.parametrize("form,width,seq,channels", [
    ("xla", 4, 24, 6), ("xla", 4, 3, 6), ("xla", 2, 9, 6),
    ("kernel", 4, 96, 256), ("kernel", 3, 64, 128), ("kernel", 2, 32, 128), ("kernel", 9, 64, 128),
])
def test_the_convolutions_written_out_backward_is_the_transposed_forward(form, width, seq, channels):
    """``causal_conv_silu``'s own rule against JAX's derivative of the same
    composition, float32: the two sum in other orders (1e-5 of the largest).
    The kernels at blocks of 32 rows: three, two and one a sequence, and
    (width 9) every row a piece carries to the next read."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, channels))
    kernel = jax.random.normal(jax.random.PRNGKey(4), (width, channels))
    g = jax.random.normal(jax.random.PRNGKey(5), (2, seq, channels))
    plain = lambda x, kernel: jax.nn.silu(causal_depthwise_conv(x, kernel))
    out, pull = jax.vjp(conv_silu(form), x, kernel)
    want, want_pull = jax.vjp(plain, x, kernel)
    assert close(out, want, 1e-6)
    for got, ref in zip(pull(g), want_pull(g)):
        assert got.shape == ref.shape and got.dtype == ref.dtype and close(got, ref, 1e-5)


def test_the_convolutions_kernels_walk_a_block_in_pieces_and_keep_bfloat16():
    """A block of 128 rows is two trips of the kernels' loop over 64 (the rows
    between them carried in registers, those between blocks in VMEM), at two
    lane pieces a block: bfloat16 in, bfloat16 out, the kernel's gradient
    float32, each within a rounding of the XLA form's."""
    x, g = (jax.random.normal(jax.random.PRNGKey(i), (1, 256, 256), jnp.bfloat16) for i in (6, 7))
    kernel = jax.random.normal(jax.random.PRNGKey(8), (4, 256)) * 0.5
    in_vmem = lambda x, kernel: conv_forms._conv_silu_in_vmem(x, kernel, 128, 256, True)
    out, pull = jax.vjp(in_vmem, x, kernel)
    want, want_pull = jax.vjp(conv_forms._conv_silu_xla, x, kernel)
    assert out.dtype == jnp.bfloat16 and close(out, want, 1e-2)
    grads, refs = pull(g), want_pull(g)
    assert [t.dtype for t in grads] == [jnp.bfloat16, jnp.float32]
    assert close(grads[0], refs[0], 1e-2) and close(grads[1], refs[1], 1e-5)


CONV_FORMS = [
    # (rows, channels, taps, dtype, on a TPU) -> the form
    ((4096, 8192, 4, jnp.bfloat16, True), {"conv": "kernel", "block_s": 1024, "block_c": 512}),  # the hybrid decoder's cell
    ((8192, 2048, 3, jnp.bfloat16, True), {"conv": "kernel", "block_s": 1024, "block_c": 512}),  # the convolution hybrid's
    ((48, 384, 2, jnp.float32, True), {"conv": "kernel", "block_s": 16, "block_c": 128}),
    ((4096, 8192, 4, jnp.bfloat16, False), {"conv": "xla", "refused": "non-TPU backend"}),
    ((4096, 8192, 4, jnp.float16, True), {"conv": "xla", "refused": "operands of float16"}),
    ((4096, 96, 4, jnp.bfloat16, True), {"conv": "xla", "refused": "96 channels are not whole lane tiles"}),
    ((24, 128, 4, jnp.bfloat16, True), {"conv": "xla", "refused": "24 rows are not whole 16-row tiles"}),
    ((64, 128, 10, jnp.bfloat16, True), {"conv": "xla", "refused": "width 10 reaches past the 8 rows a piece carries"}),
    ((64, 128, 1, jnp.bfloat16, True), {"conv": "xla", "refused": "width 1 reaches past the 8 rows a piece carries"}),
]


@pytest.mark.parametrize("shape,form", CONV_FORMS, ids=[str(shape[:3] + shape[4:]) for shape, _ in CONV_FORMS])
def test_the_convolution_picks_its_program_from_the_backend_and_the_shapes(shape, form):
    *sizes, on_tpu = shape
    assert conv_form(*sizes, on_tpu=on_tpu) == form


def test_the_dispatch_log_records_the_convolutions_form(monkeypatch):
    """One record a traced shape and fused form, beside the attention's and
    the rule's: ``xla`` with what refused on this backend, ``kernel`` with its
    blocks where a TPU would run it (nothing runs here: the trace alone writes
    the record). The gated form's shape is the ``[B, S, C]`` the taps run
    over, a third of its operand."""
    x, gates = jax.ShapeDtypeStruct((2, 64, 256), jnp.bfloat16), jax.ShapeDtypeStruct((2, 64, 384), jnp.bfloat16)
    taps = lambda channels: jax.ShapeDtypeStruct((3, channels), jnp.float32)

    def trace():
        # Fresh functions: eval_shape keeps the trace of one it has seen.
        silu = jax.eval_shape(lambda *a: causal_conv_silu(*a), x, taps(256))
        gated = jax.eval_shape(lambda *a: conv_forms.gated_causal_conv_of_thirds(*a), gates, taps(128))
        assert (silu.shape, gated.shape, gated.dtype) == ((2, 64, 256), (2, 64, 128), jnp.bfloat16)

    attention.clear_dispatch_log()
    trace()
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    trace()
    log = attention.snapshot_dispatch_log()
    attention.clear_dispatch_log()
    silu = {"op": "causal_conv", "fused": "silu", "shape": [2, 64, 256], "width": 3, "dtype": "bfloat16"}
    gated = {**silu, "fused": "gated", "shape": [2, 64, 128]}
    assert log == [
        {**silu, "conv": "xla", "refused": "non-TPU backend"},
        {**gated, "conv": "xla", "refused": "non-TPU backend"},
        {**silu, "conv": "kernel", "block_s": 64, "block_c": 256},
        {**gated, "conv": "kernel", "block_s": 64, "block_c": 128},
    ]


@pytest.mark.parametrize("dtype,width,seq,value_ch,tol", [
    (jnp.float32, 4, 96, 256, 1e-5), (jnp.float32, 3, 32, 128, 1e-5), (jnp.bfloat16, 4, 64, 256, 1e-2),
])
def test_the_convolution_reads_a_projection_by_key_head_where_it_lies(dtype, width, seq, value_ch, tol):
    """The kernels on ``[q | k | v | z]`` a key head (two heads, blocks of 32
    rows: three, one and two a sequence) against the joined form: q, k and v
    each an array of its own, z as it came; the projection's gradient whole,
    z's cotangent in z's lanes; the kernel's gradient in the leaf's order
    (all q, all k, all v)."""
    key_heads, key_ch = 2, 128
    parts = (key_ch, key_ch, value_ch, value_ch)
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    qkvz = jax.random.normal(keys[0], (2, seq, key_heads * sum(parts))).astype(dtype)
    kernel = jax.random.normal(keys[1], (width, key_heads * sum(parts[:3]))) * 0.5
    g = tuple(jax.random.normal(k, (2, seq, key_heads * ch)).astype(dtype) for k, ch in zip(keys[2:], parts))
    in_place = lambda qkvz, kernel: conv_forms._conv_silu_of_key_heads(
        qkvz, kernel, key_heads, key_ch, value_ch, 32, True)
    out, pull = jax.vjp(in_place, qkvz, kernel)
    joined = lambda qkvz, kernel: conv_forms.conv_silu_joined(
        conv_forms._conv_silu_xla, qkvz, kernel, key_heads, key_ch, value_ch)
    want, want_pull = jax.vjp(joined, qkvz, kernel)
    for got, ref in zip(out + pull(g), want + want_pull(g)):
        assert got.shape == ref.shape and got.dtype == ref.dtype and close(got, ref, tol)
    assert np.array_equal(np.asarray(out[3]), np.asarray(want[3]))  # z


KEY_HEAD_FORMS = [
    # (d_k, r d_v, rows) -> what the kernel form adds
    ((128, 256, 4096), {"block_s": 1024, "block_c": 512, "reads": "in_place"}),  # the hybrid decoder's cell
    ((256, 1024, 4096), {"block_s": 256, "block_c": 1536, "reads": "in_place"}),  # 2,560 lanes a key head
    ((64, 128, 4096), {"block_s": 1024, "block_c": 512, "reads": "joined"}),  # q and k are half a lane tile
]


@pytest.mark.parametrize("sizes,added", KEY_HEAD_FORMS, ids=[str(sizes) for sizes, _ in KEY_HEAD_FORMS])
def test_the_convolution_reads_key_heads_in_place_where_their_parts_are_lane_tiles(sizes, added):
    key_ch, value_ch, seq = sizes
    channels = 16 * (2 * key_ch + value_ch)
    form = conv_form(seq, channels, 4, jnp.bfloat16, key_head=(key_ch, value_ch), on_tpu=True)
    assert form == {"conv": "kernel", **added}
    assert conv_form(seq, channels, 4, jnp.bfloat16, key_head=(key_ch, value_ch), on_tpu=False) == {
        "conv": "xla", "refused": "non-TPU backend"}


def test_the_block_runs_the_kernels_on_its_projection_and_notes_it(monkeypatch):
    """The block with ``conv_form`` told it is on a TPU (the kernels in the
    interpreter, everything else as on this CPU) against the block as it runs
    here: the same output and the same gradient of every leaf, and a record
    of the program that read the projection in place."""
    import types
    from sav_tpu.ops import causal_conv as conv_kernels

    block = GatedDeltaNetBlock(key_heads=1, heads=2, key_ch=128, value_ch=128, chunk=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    variables = jax.jit(block.init)({"params": jax.random.PRNGKey(1)}, x)
    loss = lambda params, x: jnp.sum(jnp.square(block.apply({"params": params}, x)[0]))
    both = lambda: jax.jit(jax.value_and_grad(loss))(variables["params"], x)  # a trace a call: the form is picked in it
    want = both()
    attention.clear_dispatch_log()
    monkeypatch.setattr(conv_kernels, "_attention", types.SimpleNamespace(_on_tpu=lambda: True))
    got = both()
    log = [line for line in attention.snapshot_dispatch_log() if line["op"] == "causal_conv"]
    attention.clear_dispatch_log()
    assert log == [{"op": "causal_conv", "fused": "silu", "shape": [2, 32, 512], "width": 4, "dtype": "float32",
                    "conv": "kernel", "block_s": 32, "block_c": 512, "reads": "in_place"}]
    flat = lambda tree: jax.tree_util.tree_flatten_with_path(tree)[0]
    for (path, one), (_, ref) in zip(flat(got), flat(want)):
        # The decay's two leaves sum a sequence's terms of both signs: XLA's own two orders differ by 2e-4.
        assert close(one, ref, 2e-3 if "A_log" in (name := jax.tree_util.keystr(path)) or "dt_bias" in name else 1e-5), name


def test_the_block_hands_the_rule_its_arrays_where_they_lie(monkeypatch):
    """The block with ``rule_form`` told it is on a TPU (the operands' kernels
    in the interpreter; chunks of 16 at two value heads a key head are 32
    lanes wide, so the state-free part stays XLA's) against the block as it
    runs here: the same output, stats and gradient of every leaf, and the
    record says who computed the operands."""
    import types
    from sav_tpu.ops import gated_delta as rule_ops

    block = GatedDeltaNetBlock(key_heads=1, heads=2, key_ch=128, value_ch=128, chunk=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))  # a ragged last chunk
    variables = jax.jit(block.init)({"params": jax.random.PRNGKey(1)}, x)

    def loss(params, x):
        out, stats = block.apply({"params": params}, x)
        return jnp.sum(jnp.square(out)), stats

    both = lambda: jax.jit(jax.value_and_grad(loss, has_aux=True))(variables["params"], x)
    want = both()
    attention.clear_dispatch_log()
    monkeypatch.setattr(rule_ops, "_attention", types.SimpleNamespace(
        _on_tpu=lambda: True, log_rule_form=attention.log_rule_form))
    got = both()
    log = [line for line in attention.snapshot_dispatch_log() if line["op"] == "gated_delta_rule"]
    attention.clear_dispatch_log()
    assert log == [{"op": "gated_delta_rule", "shape": [2, 40, 1, 128], "value_heads": 2, "chunk": 16, "dtype": "float32",
                    "rule": "xla", "refused": "2 value heads a key head x chunk 16 = 32 lanes",
                    "operands": "kernel", "operands_tile": 3}]
    flat = lambda tree: jax.tree_util.tree_flatten_with_path(tree)[0]
    for (path, one), (_, ref) in zip(flat(got), flat(want)):
        assert close(one, ref, 1e-5), jax.tree_util.keystr(path)


def test_the_fused_projection_is_split_by_key_head():
    """Every output channel carries its own index: key head ``j``'s slice is
    ``[q | k | v of value heads 2j, 2j + 1 | z of the same]``."""
    key_heads, dk, heads, dv = 2, 3, 4, 5
    width = 2 * dk + 2 * 2 * dv  # a key head's slice
    qkvz = jnp.arange(key_heads * width, dtype=jnp.float32)[None, None, :]
    ba = jnp.arange(2 * heads, dtype=jnp.float32)[None, None, :]
    q, k, v, z, b, a = split_by_key_head(qkvz, ba, key_heads, dk, heads, dv)
    assert (q.shape, k.shape, v.shape, z.shape) == ((1, 1, 2, 3), (1, 1, 2, 3), (1, 1, 4, 5), (1, 1, 4, 5))
    for j in range(key_heads):
        at = j * width
        assert list(q[0, 0, j]) == list(range(at, at + dk))
        assert list(k[0, 0, j]) == list(range(at + dk, at + 2 * dk))
        assert list(v[0, 0, 2 * j]) == list(range(at + 2 * dk, at + 2 * dk + dv))
        assert list(v[0, 0, 2 * j + 1]) == list(range(at + 2 * dk + dv, at + 2 * dk + 2 * dv))
        assert list(z[0, 0, 2 * j]) == list(range(at + 2 * dk + 2 * dv, at + 2 * dk + 3 * dv))
    assert list(b[0, 0]) == [0, 1, 4, 5] and list(a[0, 0]) == [2, 3, 6, 7]


def test_the_blocks_leaves_scopes_and_stats():
    block = GatedDeltaNetBlock(key_heads=2, heads=4, key_ch=16, value_ch=16, chunk=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    variables = jax.jit(block.init)({"params": jax.random.PRNGKey(1)}, x)
    shapes = jax.tree.map(lambda leaf: leaf.shape, variables["params"])
    assert shapes == {
        "A_log": (4,), "dt_bias": (4,), "conv": {"kernel": (4, 2 * 2 * 16 + 4 * 16)},
        "gate_norm": {"scale": (16,)}, "to_out": {"kernel": (4, 16, 32)},
        "to_qkv": {"qkvz": {"kernel": (32, 2 * (2 * 16 + 2 * 2 * 16))}, "ba": {"kernel": (32, 8)}},
    }
    out, stats = jax.jit(block.apply)(variables, x)
    assert out.shape == x.shape and set(stats) == {"decay_min", "state_rms_max"}
    assert 0.0 <= float(stats["decay_min"]) <= 1.0 and float(stats["state_rms_max"]) > 0.0
    text = jax.jit(lambda v, x: block.apply(v, x)[0]).lower(variables, x).as_text(debug_info=True)
    for scope in ("gdn/conv", "gdn/rule", "gdn/gate_norm", "to_qkv", "to_out"):
        assert scope in text, scope
    assert "SelfAttentionBlock" not in text  # the attention readers pass the block by
