"""Pallas flash attention vs XLA reference numerics (BASELINE.json north star:
'every models/*_test.py cross-checks Pallas vs. XLA numerics')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from sav_tpu.ops import flash_attention, xla_attention, relative_logits_2d
from sav_tpu.ops.attention import dot_product_attention
from sav_tpu.ops.relative import rel_to_abs

# sav_tpu.ops re-exports the function under the module's name.
flmod = importlib.import_module("sav_tpu.ops.flash_attention")



def _pallas_calls(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == "pallas_call"
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                total += _pallas_calls(inner)
    return total


def _qkv(b=2, lq=197, lk=None, h=4, d=64, dtype=jnp.float32, seed=0):
    lk = lk or lq
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, lq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, lk, h, d), dtype)
    v = jax.random.normal(ks[2], (b, lk, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "lq,lk,d",
    [
        (197, 197, 64),  # ViT-B/16 @ 224
        (128, 128, 128),  # aligned
        (50, 50, 32),  # ViT @ 32x32-ish, tiny head dim
        (1, 197, 64),  # class attention: single query row
        (196, 49, 64),  # CvT: downsampled K/V
        (785, 785, 40),  # TNT-B outer-ish, odd head dim
    ],
)
@pytest.mark.slow
def test_flash_matches_xla(lq, lk, d):
    q, k, v = _qkv(lq=lq, lk=lk, d=d)
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_flash_with_bias_matches_xla():
    q, k, v = _qkv(lq=64, lk=64, d=32)
    bias = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 64, 64))
    ref = xla_attention(q, k, v, bias)
    out = flash_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_flash_with_shared_bias():
    q, k, v = _qkv(lq=33, lk=33, d=16)
    bias = jax.random.normal(jax.random.PRNGKey(9), (1, 1, 33, 33))
    ref = xla_attention(q, k, v, bias)
    out = flash_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_flash_gradients_match_xla():
    q, k, v = _qkv(lq=50, lk=50, d=32)
    bias = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 50, 50))

    def loss_f(fn):
        return lambda q, k, v, b: jnp.sum(jnp.square(fn(q, k, v, b)))

    gf = jax.grad(loss_f(flash_attention), argnums=(0, 1, 2, 3))(q, k, v, bias)
    gx = jax.grad(loss_f(xla_attention), argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


def _grad_loss(fn, **kw):
    return jax.grad(lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v, **kw))), argnums=(0, 1, 2))


@pytest.fixture(params=["one_kernel", "two_kernels"])
def backward_form(request, monkeypatch):
    """Run a case through each form of the blocked backward: the rule's own
    choice at these sizes (one kernel), and the two kernels it falls back to
    (a budget nothing fits: the program has no option for the form)."""
    if request.param == "two_kernels":
        monkeypatch.setattr(flmod, "ONE_KERNEL_VMEM_BUDGET", 0)
    return request.param


@pytest.mark.parametrize(
    "lq,lk,d,blk",
    [
        (197, 197, 64, None),  # DeiT-S/16 @ 224 — the flagship backward shape
        (128, 128, 128, None),  # aligned
        (50, 50, 32, None),  # unaligned: padded q rows + kv cols in both kernels
        (1, 197, 64, None),  # class attention: single query row
        (196, 49, 64, None),  # CvT: downsampled K/V
        # Explicit 128 blocks: with the default 256 these lengths would be
        # single-block, silently skipping the cross-block accumulation
        # protocol (ki==0 init / last-ki write) this case exists to cover.
        (320, 256, 40, 128),  # multi-block q and kv, odd head dim
    ],
)
@pytest.mark.slow
def test_flash_blocked_backward_matches_xla(backward_form, lq, lk, d, blk):
    """No-bias gradients run the blocked Pallas backward kernels."""
    q, k, v = _qkv(lq=lq, lk=lk, d=d)
    kw = {} if blk is None else {"block_q": blk, "block_kv": blk}
    gf = _grad_loss(flash_attention, **kw)(q, k, v)
    gx = _grad_loss(xla_attention)(q, k, v)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=5e-4
        )


# (lq, d, dv, causal, block_q, block_kv, block_b): every case has several q
# and several kv blocks, so the one-kernel form comes back to each q block's
# resident dq once per kv block (and skips the blocks above the diagonal).
BOTH_FORMS_CASES = [
    (256, 128, 128, True, 64, 64, 1),  # the looped model's head size
    (256, 128, 128, False, 64, 64, 1),
    (192, 192, 128, True, 64, 64, 1),  # the latent pair: a 192-lane q/k head left at 192
    (192, 192, 128, False, 64, 64, 1),
    (200, 64, 64, True, 64, 64, 1),  # padded q rows and kv columns
    (200, 64, 64, False, 64, 128, 1),  # padded, and a kv block of two q blocks
    (200, 48, 32, True, 128, 64, 2),  # block_b 2, a q block of two kv blocks, heads padded apart
    (256, 128, 128, True, 64, 64, 2),
]


@pytest.mark.parametrize("lq,d,dv,causal,block_q,block_kv,block_b", BOTH_FORMS_CASES)
def test_flash_blocked_backward_matches_xla_in_both_forms(
    backward_form, lq, d, dv, causal, block_q, block_kv, block_b
):
    """dq, dk, dv of the one-kernel backward and of the two kernels against
    the dense path's, to the tolerances of the cases above."""
    q, k, _ = _qkv(b=1, lq=lq, h=2, d=d)
    v = jax.random.normal(jax.random.PRNGKey(3), (1, lq, 2, dv))
    grad = _grad_loss(
        flash_attention, causal=causal, block_q=block_q, block_kv=block_kv, block_b=block_b
    )
    jaxpr = jax.make_jaxpr(grad)(q, k, v)
    assert _pallas_calls(jaxpr.jaxpr) == (2 if backward_form == "one_kernel" else 3)
    gx = _grad_loss(xla_attention, causal=causal)(q, k, v)
    for a, b in zip(grad(q, k, v), gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=5e-4)


def _bf16_backward_close(**kw):
    q, k, v = _qkv(lq=197, lk=197, d=64, dtype=jnp.bfloat16)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v).astype(jnp.float32)))

    gf = jax.grad(lambda *a: loss(lambda *b: flash_attention(*b, **kw), *a), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(lambda *a: loss(xla_attention, *a), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gx):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        # bf16 tolerance: both paths quantize differently.
        np.testing.assert_allclose(a, b, atol=0.15, rtol=0.15)


@pytest.mark.slow
def test_flash_blocked_backward_bf16_finite_and_close():
    _bf16_backward_close()


def test_flash_blocked_backward_bf16_finite_and_close_in_both_forms(backward_form):
    """bf16 operands, several blocks each way: ``p`` and ``ds`` go to the
    three output matmuls in bf16 and every sum stays float32, in both forms."""
    _bf16_backward_close(block_q=64, block_kv=64)


def test_backward_form_follows_what_the_resident_dq_needs():
    """The rule's two sides, from sizes alone: the token cells' shapes at
    their tuned tiles fit (the whole float32 dq of a batch·head cell beside
    the tiles), a ring shard's 65,536 rows do not, and ``block_b`` counts."""
    cell = dict(batch_heads=64, block_q=1024, block_kv=1024, block_b=1)
    assert flmod.backward_form(4096, 4096, 192, 128, **cell) == "one_kernel"
    assert flmod.backward_form(4096, 4096, 128, 128, **cell) == "one_kernel"
    assert flmod.backward_form(65536, 65536, 128, 128, **cell) == "two_kernels"
    assert flmod.backward_form(16384, 16384, 64, 64, batch_heads=12, block_b=1) == "one_kernel"
    assert flmod.backward_form(16384, 16384, 64, 64, batch_heads=12) == "two_kernels"  # block_b 4 by default
    # dq alone: 65,536 rows x 128 lanes x (4 bytes resident + 2 x 2 out) is 64 MiB.
    small = dict(block_q=128, block_kv=128, block_b=1)
    assert flmod.one_kernel_backward_vmem_bytes(65536, 128, 128, **small) > 64 * 2**20 > flmod.ONE_KERNEL_VMEM_BUDGET
    assert flmod.one_kernel_backward_vmem_bytes(4096, 192, 128, block_q=1024, block_kv=1024) < flmod.ONE_KERNEL_VMEM_BUDGET
    assert flmod.ONE_KERNEL_VMEM_BUDGET < flmod._ONE_KERNEL_VMEM_LIMIT <= 128 * 2**20


def test_a_dq_that_does_not_fit_runs_the_two_kernels(monkeypatch):
    """The same call on each side of the rule: a budget between the two
    lengths' needs sends the longer one to two calls, with the same
    gradients to float32 rounding."""
    need = lambda lq: flmod.one_kernel_backward_vmem_bytes(lq, 128, 128, block_q=64, block_kv=64, itemsize=4)
    monkeypatch.setattr(flmod, "ONE_KERNEL_VMEM_BUDGET", (need(128) + need(512)) // 2)
    grad = _grad_loss(flash_attention, causal=True, block_q=64, block_kv=64, block_b=1)
    calls = {}
    for lq in (128, 512):
        q, k, v = _qkv(b=1, lq=lq, h=1, d=128)
        calls[lq] = _pallas_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)
    assert calls == {128: 2, 512: 3}
    gx = _grad_loss(xla_attention, causal=True)(q, k, v)
    for a, b in zip(grad(q, k, v), gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=5e-4)


def test_dispatch_log_names_the_backward_the_pallas_entry_runs(monkeypatch):
    from sav_tpu.ops import attention as att

    q, k, v = _qkv(lq=64, lk=64, d=32)
    bias = jnp.zeros((1, 1, 64, 64))
    whole = _qkv(b=1, lq=128, h=1, d=128)  # one slice a cell, a head of one lane tile, whole blocks
    att.clear_dispatch_log()
    dot_product_attention(q, k, v, backend="pallas")
    dot_product_attention(q[:, :32], k, v, bias[:, :, :32], backend="pallas")  # biased: the dense recompute
    dot_product_attention(q, k, v, backend="xla")
    dot_product_attention(*whole, backend="pallas", causal=True)
    monkeypatch.setattr(flmod, "ONE_KERNEL_VMEM_BUDGET", 0)
    dot_product_attention(q, k[:, :48], v[:, :48], backend="pallas")
    dot_product_attention(*whole, backend="pallas")
    log = {
        (e["shape"][1], e["kv_len"], e["backend"], e["requested"] + str(e["shape"][3])): (e.get("backward"), e.get("layout"))
        for e in att.snapshot_dispatch_log()
    }
    att.clear_dispatch_log()
    assert log == {
        (64, 64, "pallas", "pallas32"): ("one_kernel", "head_major"),
        (32, 64, "pallas", "pallas32"): (None, "head_major"),
        (64, 64, "xla", "xla32"): (None, None),
        (128, 128, "pallas", "pallas128"): ("one_kernel", "in_place"),
        (64, 48, "pallas", "pallas32"): ("two_kernels", "head_major"),
    }


# (what differs from a [B 2, L 512, H 1, D 128 / 128] call at blocks of 128 with one slice a cell) -> the layout
LAYOUT_CASES = [
    ({}, "in_place"),
    ({"dim": 256, "dim_v": 256}, "in_place"),
    ({"dim": 256}, "in_place"),  # a value head of its own size, both whole lane tiles
    ({"block_q": 1024, "block_kv": 1024}, "in_place"),  # blocks clamped to the sequence: one whole block
    ({"q_len": 4096, "kv_len": 4096, "batch_heads": 32, "block_q": 1024, "block_kv": 1024}, "in_place"),  # the looped model's cell
    ({"q_len": 4096, "kv_len": 4096, "batch_heads": 32, "block_q": 2048, "block_kv": 1024}, "in_place"),  # and a tile over Mosaic's default VMEM: both calls carry the limit
    ({"dim": 64, "dim_v": 64}, "head_major"),  # half a lane tile
    ({"dim": 192}, "head_major"),  # the latent pair: no 192-lane block out of a wider array
    ({"q_len": 200, "kv_len": 200}, "head_major"),  # rows to pad
    ({"kv_len": 448}, "head_major"),  # kv columns to pad
    ({"q_len": 64, "kv_len": 64}, "head_major"),  # blocks that are no whole lane tiles (their rows lie on the lanes)
    ({"kv_len": 192, "block_kv": 64}, "head_major"),
    ({"biased": True}, "head_major"),
    ({"block_b": None}, "head_major"),  # two slices a cell by default
    ({"block_b": 2}, "head_major"),
    ({"q_len": 65536, "kv_len": 65536}, "head_major"),  # a ring shard: the two-kernel backward
]


@pytest.mark.parametrize("change,layout", LAYOUT_CASES, ids=[",".join(f"{k}={v}" for k, v in c.items()) or "base" for c, _ in LAYOUT_CASES])
def test_layout_form_follows_what_a_block_can_address(change, layout):
    """The rule's two sides, from sizes, blocks and whether there is a bias
    alone: in place where every block is whole lane tiles of the caller's
    arrays and the one-kernel backward runs, head-major everywhere else."""
    call = dict(q_len=512, kv_len=512, dim=128, dim_v=128, batch_heads=2, block_q=128, block_kv=128, block_b=1)
    call.update(change)
    lengths = [call.pop(name) for name in ("q_len", "kv_len", "dim", "dim_v")]
    assert flmod.layout_form(*lengths, **call) == layout
    if change.get("q_len") == 65536:
        call.pop("biased", None)
        assert flmod.backward_form(*lengths, **call) == "two_kernels"


# (d, dv, block_q, block_kv) at [B 2, L 512, H 2]: several q and kv blocks
# each way, so the causal diagonal crosses blocks and skips others.
IN_PLACE_CASES = [(128, 128, 128, 128), (128, 128, 256, 256), (128, 128, 256, 128), (256, 256, 128, 128), (256, 128, 128, 256)]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "unmasked"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("d,dv,block_q,block_kv", IN_PLACE_CASES)
def test_in_place_form_matches_xla_and_the_head_major_form(monkeypatch, d, dv, block_q, block_kv, dtype, causal):
    """Forward and the three gradients of the in-place form against the
    dense path's, and against the head-major form's of the same call (the
    rule pinned to it: the program has no option for the layout): float32
    to rounding, bf16 to the file's tolerances."""
    q, k, _ = _qkv(lq=512, h=2, d=d, dtype=dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 512, 2, dv), dtype)
    blocks = dict(block_q=block_q, block_kv=block_kv, block_b=1)
    assert flmod.layout_form(512, 512, d, dv, batch_heads=4, itemsize=q.dtype.itemsize, **blocks) == "in_place"

    def both(fn, **kw):
        def loss(q, k, v):
            out = fn(q, k, v, causal=causal, **kw)
            return jnp.sum(jnp.square(out.astype(jnp.float32))), out
        grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(a, np.float32) for a in (out, *grads)]

    in_place = both(flash_attention, **blocks)
    dense = both(xla_attention)
    monkeypatch.setattr(flmod, "layout_form", lambda *a, **kw: "head_major")
    head_major = both(flash_attention, **blocks)
    exact = dtype == jnp.float32
    for name, got, other, ref in zip(("out", "dq", "dk", "dv"), in_place, head_major, dense):
        assert np.isfinite(got).all(), name
        if name == "out":
            tol = dict(atol=2e-5, rtol=2e-5) if exact else dict(atol=3e-2, rtol=3e-2)
        else:
            tol = dict(atol=1e-4, rtol=5e-4) if exact else dict(atol=0.15, rtol=0.15)
        np.testing.assert_allclose(got, ref, err_msg=name, **tol)
        np.testing.assert_allclose(got, other, err_msg=name, **tol)


def _equations(jaxpr):
    """Every equation outside the kernels' bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _equations(inner)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "unmasked"])
def test_in_place_form_leaves_xla_nothing_to_copy(causal):
    """Around the in-place form's two calls the traced program pads nothing,
    broadcasts nothing (the cotangent's sum is the test's own) and holds no
    float32 array with a lane tile a row, inside or outside a call's
    operands. Its only transposes say that q, k, v and dO have the sequence
    on the lanes, ``(0, 2, 3, 1)``: the order XLA holds a projection's
    output in, so it answers them with a layout and moves nothing
    (``tests/test_tpu_compile.py`` holds that of the compiled step). The
    head-major form of the same call does all three."""
    # A head of two lane tiles: a trailing axis of 128 is then no head.
    q, k, v = _qkv(b=1, lq=256, h=2, d=256, dtype=jnp.bfloat16)
    grad = _grad_loss(flash_attention, causal=causal, block_q=128, block_kv=128, block_b=1)

    def moved(jaxpr):
        copies, tiles = [], []
        for eqn in _equations(jaxpr.jaxpr):
            avals = [var.aval for var in (*eqn.invars, *eqn.outvars) if hasattr(var.aval, "shape")]
            tiles += [a.shape for a in avals if a.dtype == jnp.float32 and a.ndim > 1 and a.shape[-1] == 128]
            if eqn.primitive.name in ("transpose", "pad", "broadcast_in_dim") and avals[-1].size >= q.size:
                copies.append((eqn.primitive.name, tuple(eqn.params.get("permutation", ()))))
        return copies, tiles

    jaxpr = jax.make_jaxpr(grad)(q, k, v)
    assert _pallas_calls(jaxpr.jaxpr) == 2
    copies, tiles = moved(jaxpr)
    # q, k, v forward; q, k, v, dO backward; d(sum): the loss's own cotangent.
    assert sorted(copies) == [("broadcast_in_dim", ())] + [("transpose", (0, 2, 3, 1))] * 7, copies
    assert not tiles, tiles
    padded = jax.make_jaxpr(_grad_loss(flash_attention, causal=causal, block_q=128, block_kv=128, block_b=2))(q, k, v)
    copies, tiles = moved(padded)
    assert copies.count(("transpose", (0, 2, 1, 3))) == 11 and tiles, (copies, tiles)


@pytest.mark.slow
def test_flash_bf16():
    q, k, v = _qkv(lq=197, lk=197, d=64, dtype=jnp.bfloat16)
    ref = xla_attention(q, k, v)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


@pytest.mark.slow
def test_flash_softmax_stability():
    """Large logit magnitudes must not overflow the online softmax."""
    q, k, v = _qkv(lq=64, lk=64, d=32)
    out = flash_attention(100.0 * q, 100.0 * k, v)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
def test_dispatch_backends_agree():
    q, k, v = _qkv(lq=60, lk=60, d=16)
    out_x = dot_product_attention(q, k, v, backend="xla")
    out_p = dot_product_attention(q, k, v, backend="pallas")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=2e-5, rtol=2e-5)


def test_dispatch_rejects_bad_backend():
    q, k, v = _qkv(lq=8, lk=8, d=8)
    with pytest.raises(ValueError, match="unknown attention backend"):
        dot_product_attention(q, k, v, backend="cuda")


def test_rel_to_abs_indexing():
    length = 9
    x = jax.random.normal(jax.random.PRNGKey(0), (2, length, 2 * length - 1))
    y = np.asarray(rel_to_abs(x))
    xn = np.asarray(x)
    for i in range(length):
        for j in range(length):
            np.testing.assert_allclose(y[0, i, j], xn[0, i, j - i + length - 1], rtol=1e-6)


def test_relative_logits_2d_offsets():
    """Entry [x,y,X,Y] must equal q[x,y]·rel_h[X-x+H-1] + q[x,y]·rel_w[Y-y+W-1]."""
    h_, w_, d = 3, 4, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, h_, w_, d))
    rel_h = jax.random.normal(jax.random.PRNGKey(1), (2 * h_ - 1, d))
    rel_w = jax.random.normal(jax.random.PRNGKey(2), (2 * w_ - 1, d))
    out = np.asarray(relative_logits_2d(q, rel_h, rel_w))
    qn, rh, rw = map(np.asarray, (q, rel_h, rel_w))
    for x in range(h_):
        for y in range(w_):
            for xx in range(h_):
                for yy in range(w_):
                    expected = qn[0, 0, x, y] @ rh[xx - x + h_ - 1] + qn[0, 0, x, y] @ rw[
                        yy - y + w_ - 1
                    ]
                    np.testing.assert_allclose(
                        out[0, 0, x, y, xx, yy], expected, rtol=1e-4
                    )


# ------------------------------------------------- talking-heads (CaiT)


@pytest.mark.parametrize("lq,lk,h,d", [(196, 196, 4, 48), (50, 50, 2, 32)])
@pytest.mark.slow
def test_talking_heads_fused_matches_xla(lq, lk, h, d):
    from sav_tpu.ops.talking_heads import (
        _th_dense_reference,
        flash_talking_heads_attention,
    )

    q, k, v = _qkv(lq=lq, lk=lk, h=h, d=d)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    w_pre = jax.nn.initializers.orthogonal()(ks[0], (h, h))
    w_post = jax.nn.initializers.orthogonal()(ks[1], (h, h))
    ref = _th_dense_reference(q, k, v, w_pre, w_post, d ** -0.5)
    out = flash_talking_heads_attention(q, k, v, w_pre, w_post)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5, rtol=5e-5)


@pytest.mark.slow
def test_talking_heads_fused_gradients_match_dense():
    from sav_tpu.ops.talking_heads import (
        _th_dense_reference,
        flash_talking_heads_attention,
    )

    q, k, v = _qkv(lq=40, lk=40, h=2, d=16)
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    w_pre = jax.nn.initializers.orthogonal()(ks[0], (2, 2))
    w_post = jax.nn.initializers.orthogonal()(ks[1], (2, 2))

    def loss(fn):
        return lambda *a: jnp.sum(jnp.square(fn(*a)))

    gf = jax.grad(loss(lambda *a: flash_talking_heads_attention(*a)), argnums=(0, 1, 2, 3, 4))(
        q, k, v, w_pre, w_post
    )
    gx = jax.grad(loss(lambda *a: _th_dense_reference(*a, 16 ** -0.5)), argnums=(0, 1, 2, 3, 4))(
        q, k, v, w_pre, w_post
    )
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


@pytest.mark.slow
def test_talking_heads_blocked_backward_multi_qblock():
    """block_q < q_len drives the backward's dk/dv/dW accumulation across
    sequential q-block grid cells (and the zero-padded final block)."""
    from sav_tpu.ops.talking_heads import (
        _th_dense_reference,
        flash_talking_heads_attention,
        fused_bwd_eligible,
    )

    assert fused_bwd_eligible(heads=3, q_len=40, kv_len=40, dim=16, block_q=16)
    q, k, v = _qkv(lq=40, lk=40, h=3, d=16)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    w_pre = jax.nn.initializers.orthogonal()(ks[0], (3, 3))
    w_post = jax.nn.initializers.orthogonal()(ks[1], (3, 3))

    def loss(fn):
        return lambda *a: jnp.sum(jnp.square(fn(*a)))

    gf = jax.grad(
        loss(lambda *a: flash_talking_heads_attention(*a, block_q=16)),
        argnums=(0, 1, 2, 3, 4),
    )(q, k, v, w_pre, w_post)
    gx = jax.grad(
        loss(lambda *a: _th_dense_reference(*a, 16 ** -0.5)),
        argnums=(0, 1, 2, 3, 4),
    )(q, k, v, w_pre, w_post)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


def test_talking_heads_fused_rejects_over_budget_shapes():
    from sav_tpu.ops.talking_heads import (
        flash_talking_heads_attention,
        fused_eligible,
    )

    # Many heads × long kv blows the VMEM working set (the CaiT-M-at-high-res
    # class of shapes) — must raise, and the auto gate must say ineligible.
    assert not fused_eligible(heads=16, kv_len=2026, dim=64)
    assert fused_eligible(heads=4, kv_len=196, dim=48)  # CaiT-XXS24 trunk
    q, k, v = _qkv(lq=8, lk=2026, h=16, d=64)
    w = jnp.eye(16)
    with pytest.raises(ValueError, match="VMEM"):
        flash_talking_heads_attention(q, k, v, w, w)


def test_talking_heads_block_kernel_accessor():
    """TalkingHeadsBlock(None) returns the kernel with the same param tree."""
    from sav_tpu.models.layers.attention import TalkingHeadsBlock

    block = TalkingHeadsBlock(num_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8, 8))
    v1 = block.init(jax.random.PRNGKey(1), x)
    v2 = block.init(jax.random.PRNGKey(1), None)
    assert jax.tree.structure(v1) == jax.tree.structure(v2)
    kernel = block.apply(v1, None)
    assert kernel.shape == (4, 4)
    ref = jnp.einsum("hi,bhqk->biqk", kernel, x)
    np.testing.assert_allclose(np.asarray(block.apply(v1, x)), np.asarray(ref), rtol=1e-6)


@pytest.mark.slow
def test_dot_product_attention_xla_matches_reference():
    """Dispatcher's XLA branch runs the plain-autodiff reference path."""
    q, k, v = _qkv(lq=64, lk=64, d=32)
    out = dot_product_attention(q, k, v, backend="xla")
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_logits_dtype_default_knob():
    """`set_default_logits_dtype` switches the XLA softmax dtype process-wide
    (TrainConfig.attention_logits_dtype plumbs to it); bf16 logits must stay
    within bf16 quantization of the f32 reference."""
    from sav_tpu.ops import attention as att

    q, k, v = _qkv(lq=32, lk=32, d=16, dtype=jnp.bfloat16)
    ref = np.asarray(att.xla_attention(q, k, v), np.float32)
    att.set_default_logits_dtype("bfloat16")
    try:
        lo = np.asarray(att.xla_attention(q, k, v), np.float32)
    finally:
        att.set_default_logits_dtype("float32")
    assert np.all(np.isfinite(lo))
    denom = np.maximum(np.abs(ref), 1e-2)
    assert np.median(np.abs(lo - ref) / denom) < 3e-2
    # explicit argument still overrides the default
    hi = np.asarray(att.xla_attention(q, k, v, logits_dtype=jnp.float32), np.float32)
    np.testing.assert_allclose(hi, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layout", ["head_major", "in_place"])
@pytest.mark.parametrize("kept,calls", [(("flash_out", "flash_lse"), 2), (("flash_out",), 3), ((), 3)])
def test_checkpoint_policy_keeps_the_forward_kernels_residuals(kept, calls, layout):
    """The forward rule names its output and its logsumexp: a
    ``jax.checkpoint`` policy that lists both differentiates through one
    forward kernel call (and the one backward call); one that misses either runs the
    forward a second time for it. In both layouts."""
    d, blocks = (32, dict(block_q=32, block_kv=32)) if layout == "head_major" else (128, dict(block_q=128, block_kv=128, block_b=1))
    q, k, v = _qkv(b=1, lq=4 * blocks["block_q"] // 2, h=2, d=d)
    assert flmod.layout_form(q.shape[1], q.shape[1], d, d, batch_heads=2, itemsize=4, **blocks) == layout

    def loss(q, k, v):
        out = flash_attention(jnp.sin(q), k, v, causal=True, **blocks)
        return jnp.sum(jnp.cos(out))  # out is needed again: by cos, and as the backward's residual

    policy = jax.checkpoint_policies.save_only_these_names(*kept)
    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1, 2)))(q, k, v)
    assert _pallas_calls(jaxpr.jaxpr) == calls
