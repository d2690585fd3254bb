"""The band in the flash kernels (``window`` beside ``causal``): forward and
all three gradients against the dense path in the interpreter, in the resident
pair (a grid that is the band) and in the causal kernels' banded arm in each
layout and backward form, with the window's far edge inside a block, on a
block boundary, shorter than a block and no shorter than the sequence; which
shapes take which; and that a call without a window, or one the resident pair
does not take, builds what it built before."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sav_tpu.ops import attn_tuning
from sav_tpu.ops.attention import (
    causal_mask,
    clear_dispatch_log,
    dot_product_attention,
    resolve_attention_backend,
    snapshot_dispatch_log,
    xla_attention,
)

flmod = importlib.import_module("sav_tpu.ops.flash_attention")
flash_attention = flmod.flash_attention


def _qkv(length, heads, kv_heads, dim, seed=0, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, length, heads, dim), jnp.float32)
    k = jax.random.normal(ks[1], (batch, length, kv_heads, dim), jnp.float32)
    v = jax.random.normal(ks[2], (batch, length, kv_heads, dim), jnp.float32)
    return q, k, v


def _loss_and_grads(fn, q, k, v, **kw):
    weight = jax.random.normal(jax.random.PRNGKey(7), q.shape[:3] + v.shape[3:], jnp.float32)
    return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v, **kw) * weight), argnums=(0, 1, 2))(q, k, v)


def _assert_matches_dense(q, k, v, window, **blocks):
    want = xla_attention(q, k, v, causal=True, window=window, logits_dtype=jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window, **blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)
    (_, want_grads), (_, got_grads) = (
        _loss_and_grads(fn, q, k, v, causal=True, window=window, **kw)
        for fn, kw in ((xla_attention, {"logits_dtype": jnp.float32}), (flash_attention, blocks))
    )
    for name, a, b in zip("qkv", got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4, err_msg=f"d{name}")


def test_the_mask_is_written_once_and_is_the_band():
    mask = np.asarray(causal_mask(12, 12, window=4))
    rows, cols = np.indices((12, 12))
    assert (mask == ((cols <= rows) & (cols > rows - 4))).all()
    assert mask[7].sum() == 4 and mask[7, 4] and not mask[7, 3]  # itself and the 3 before it
    assert (np.asarray(causal_mask(12, 12)) == (cols <= rows)).all()
    assert (np.asarray(flmod.band_keep(rows, cols, 4)) == mask).all()
    with pytest.raises(ValueError, match="window"):
        causal_mask(12, 12, window=0)
    q, k, v = _qkv(12, 2, 1, 8)
    with pytest.raises(ValueError, match="causal"):
        xla_attention(q, k, v, window=4)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=4)


@pytest.fixture(params=["resident", "skipped_cells"])
def band(request, monkeypatch):
    """Which kernels run the band at a shape the resident pair takes: its
    own, or (the rule's bound on the unrolled program pinned shut) the
    causal kernels' arm."""
    if request.param == "skipped_cells":
        monkeypatch.setattr(flmod, "BAND_MAX_UNROLLED_TILES", -1)
    return request.param


# The in-place layout: heads of whole lane tiles, sequences of whole blocks,
# one slice a cell, groups of 6 and 9 found through the index maps (the
# banded arm) or held in one cell (the resident pair).
@pytest.mark.parametrize(
    "heads,window",
    [
        (6, 128),  # the far edge on a block boundary
        (6, 200),  # inside a block, past one block
        (9, 100),  # shorter than a block: a block meets both edges
        (9, 129),  # one column into the block before
        (6, 384),
    ],
)
def test_banded_kernels_in_place_match_the_dense_path(band, heads, window):
    q, k, v = _qkv(512, heads, 1, 128, seed=window)
    blocks = dict(block_q=128, block_kv=128, block_b=1)
    assert flmod.layout_form(512, 512, 128, 128, batch_heads=heads, itemsize=4, **blocks) == "in_place"
    assert _band_form(q, k, window, 128) == band
    _assert_matches_dense(q, k, v, window, **blocks)


def _band_form(q, k, window, block, block_kv=None, dim_v=None, **kw):
    length, dim = q.shape[1], q.shape[-1]
    return flmod.band_form(
        length, k.shape[1], dim, dim_v or dim, heads=q.shape[2], kv_heads=k.shape[2], window=window,
        block_q=block, block_kv=block_kv or block, itemsize=q.dtype.itemsize, **kw,
    )


# The resident pair: (length, heads, key/value heads, window, block, batch).
RESIDENT_CASES = {
    "group6_window_on_a_block_boundary": (1024, 6, 1, 512, 256, 1),
    "group9_window_inside_the_second_block": (1024, 9, 1, 384, 256, 1),
    "group9_window_inside_the_third_block": (1024, 9, 1, 640, 256, 1),
    "group1_five_blocks_of_128": (1024, 2, 2, 512, 128, 1),
    "group6_window_shorter_than_a_block": (512, 6, 1, 100, 256, 1),
    "group9_window_one_block_less_than_the_sequence": (512, 9, 1, 384, 128, 1),
    "group3_window_one_position_less_than_the_sequence": (512, 3, 1, 511, 128, 1),
    "group3_blocks_of_512": (1024, 6, 2, 512, 512, 1),
    "group3_two_sequences_a_batch": (512, 6, 2, 200, 128, 2),
    "group2_a_window_of_one": (256, 2, 1, 1, 128, 1),
}


@pytest.mark.parametrize("case", list(RESIDENT_CASES.values()), ids=list(RESIDENT_CASES))
def test_resident_pair_matches_the_dense_path(case):
    length, heads, kv_heads, window, block, batch = case
    q, k, v = _qkv(length, heads, kv_heads, 128, seed=window + block, batch=batch)
    assert _band_form(q, k, window, block) == "resident"
    _assert_matches_dense(q, k, v, window, block_q=block, block_kv=block)


@pytest.mark.parametrize("window,block", [(512, 256), (640, 256), (300, 128)])
def test_the_clipped_start_row_by_row(window, block):
    """The first ``resident - 1`` q blocks hold fewer kv blocks than the
    rest (a body of their own a count): each of their rows, and the first
    row of a whole cell, against the dense path, forward and dq."""
    q, k, v = _qkv(1024, 3, 1, 128, seed=window)
    resident = flmod.band_resident_blocks(1024, block, window)
    assert resident >= 3 and _band_form(q, k, window, block) == "resident"
    blocks = dict(block_q=block, block_kv=block)
    (_, want), (_, got) = (
        _loss_and_grads(fn, q, k, v, causal=True, window=window, **kw)
        for fn, kw in ((xla_attention, {"logits_dtype": jnp.float32}), (flash_attention, blocks))
    )
    out = flash_attention(q, k, v, causal=True, window=window, **blocks)
    dense = xla_attention(q, k, v, causal=True, window=window, logits_dtype=jnp.float32)
    for row in range((resident - 1) * block + 1):
        np.testing.assert_allclose(np.asarray(out[0, row]), np.asarray(dense[0, row]), atol=3e-5, rtol=3e-5, err_msg=f"row {row}")
        np.testing.assert_allclose(np.asarray(got[0][0, row]), np.asarray(want[0][0, row]), atol=2e-4, rtol=2e-4, err_msg=f"dq row {row}")


def _pallas_grids(jaxpr) -> list:
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                grids.extend(_pallas_grids(inner))
    return grids


@pytest.mark.parametrize("window,block", [(512, 256), (512, 512), (512, 128), (384, 256), (100, 256), (1025, 256)])
def test_the_resident_grid_is_the_band(window, block):
    """No cell without work: a q block's cell holds the kv blocks it sees,
    every one with a visible pair but the clipped ones before the
    sequence's start, and the backward adds ``resident - 1`` flush cells."""
    length, heads, kv_heads = 4096, 18, 2
    resident = flmod.band_resident_blocks(length, block, window)
    n = length // block
    cells = flmod.band_cells(length, length, block_q=block, block_kv=block, window=window, form="resident")
    band_count = flmod.band_blocks(n, n, block, block, window)["visited"]
    clipped = resident * (resident - 1) // 2
    assert cells["kv_blocks_visited"] == band_count == n * resident - clipped
    assert cells["kv_blocks_grid"] - clipped == band_count and cells["flush_cells"] == resident - 1
    # Each of a cell's blocks has work, and the block one further back has none.
    mask = np.asarray(causal_mask(length, length, window)).reshape(n, block, n, block).any(axis=(1, 3))
    for i in range(n):
        assert [t for t in range(i + 1) if mask[i, i - t]] == list(range(min(i + 1, resident)))
    # The mask a tile takes: the diagonal's block ``col <= row`` alone, the blocks the far edge crosses its comparison.
    whole = np.asarray(causal_mask(length, length, window))
    i = n - 1
    for t in range(resident):
        tile = whole[i * block:(i + 1) * block, (i - t) * block:(i - t + 1) * block]
        diagonal, far = flmod._band_tile_edges(t, block, window)
        assert (diagonal or far) == (not tile.all()) and tile.any()
        if diagonal or far:
            keep = np.asarray(flmod._causal_keep(t, 0, block, block, transposed=True, window=window, edges=(diagonal, far)))
            assert (keep.T == tile).all()
    spec = lambda h: jax.ShapeDtypeStruct((1, length, h, 128), jnp.bfloat16)
    both = jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, window=window, block_q=block, block_kv=block)
                                .astype(jnp.float32)), argnums=(0, 1, 2))
    grids = _pallas_grids(jax.make_jaxpr(both)(spec(heads), spec(kv_heads), spec(kv_heads)).jaxpr)
    assert grids == [(1, kv_heads, n), (1, kv_heads, n + resident - 1)]  # one call a direction


def _primitives_outside_the_calls(jaxpr) -> list:
    """The primitives of a traced program in order, the kernels' bodies left out."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    names.extend(_primitives_outside_the_calls(inner))
    return names


def _program(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


FALLBACK_CASES = {
    # (length, heads, key/value heads, head, blocks, biased)
    "a_head_of_64": (512, 6, 1, 64, (128, 128), False),
    "a_ragged_length": (500, 6, 1, 128, (128, 128), False),
    "a_bias": (512, 6, 1, 128, (128, 128), True),
    "unequal_blocks": (512, 6, 1, 128, (256, 128), False),
    "a_window_of_too_many_blocks": (4096, 72, 8, 128, (128, 128), False),
}


@pytest.mark.parametrize("case", list(FALLBACK_CASES.values()), ids=list(FALLBACK_CASES))
def test_shapes_the_resident_pair_does_not_take_build_the_program_of_before(case):
    """They log ``skipped_cells`` and their jaxpr is the causal kernels'
    banded arm's, letter for letter: the custom_vjp of before called with
    the arguments of before."""
    length, heads, kv_heads, dim, (block_q, block_kv), biased = case
    window = 200 if length < 4096 else 2048
    spec = lambda h: jax.ShapeDtypeStruct((1, length, h, dim), jnp.float32)
    bias = jax.ShapeDtypeStruct((1, 1, length, length), jnp.float32) if biased else None
    blocks = dict(block_q=block_q, block_kv=block_kv)
    assert flmod.band_form(
        length, length, dim, dim, heads=heads, kv_heads=kv_heads, window=window, biased=biased, itemsize=4, **blocks
    ) == "skipped_cells"
    args = (spec(heads), spec(kv_heads), spec(kv_heads)) + ((bias,) if biased else ())

    def through_the_dispatch(q, k, v, bias=None):
        return flash_attention(q, k, v, bias, causal=True, window=window, **blocks)

    def of_before(q, k, v, bias=None):
        return flmod._flash(q, k, v, bias, dim ** -0.5, block_q, block_kv, None, True, None, window)

    def with_grads(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2))

    text = _program(with_grads(through_the_dispatch), *args)
    assert text == _program(with_grads(of_before), *args) and "_band" not in text
    if block_q != block_kv:  # the dispatcher takes its blocks from the tune cache
        return
    clear_dispatch_log()
    jax.eval_shape(lambda *a: dot_product_attention(*a, causal=True, window=window, backend="pallas"), *args)
    (record,) = snapshot_dispatch_log()
    assert record["band"] == "skipped_cells" and "flush_cells" not in record
    n_q, n_kv = -(-length // 256), -(-length // 256)  # the dispatcher's default blocks
    assert record["kv_blocks_grid"] == n_q * n_kv > record["kv_blocks_visited"]


def test_the_dispatcher_takes_the_resident_pair_at_the_cells_shape():
    """``laguna.train_ep32_4k``'s window layers, at the blocks the shipped
    tune cache gives them: the record says ``resident`` with the grid's
    count beside the visited one, and the program holds the pair's two
    calls and nothing of the causal kernels'."""
    spec = lambda h: jax.ShapeDtypeStruct((1, 4096, h, 128), jnp.bfloat16)
    args = (spec(72), spec(8), spec(8))
    clear_dispatch_log()
    core = lambda q, k, v: dot_product_attention(q, k, v, causal=True, window=512, backend="pallas")
    grad = jax.value_and_grad(lambda *a: jnp.sum(core(*a).astype(jnp.float32)), argnums=(0, 1, 2))
    traced = jax.make_jaxpr(grad)(*args).jaxpr
    (record,) = snapshot_dispatch_log()
    assert record["band"] == "resident" and record["window"] == 512
    assert (record["layout"], record["backward"], record["grouped_kv"]) == ("in_place", "one_kernel", "in_cell")
    block = record["block_config"]["block_q"]
    resident = flmod.band_resident_blocks(4096, block, 512)
    assert record["kv_blocks_grid"] == 4096 // block * resident
    assert record["kv_blocks_grid"] - resident * (resident - 1) // 2 == record["kv_blocks_visited"]
    assert record["flush_cells"] == resident - 1
    blocks = 4096 // block
    assert _pallas_grids(traced) == [(1, 8, blocks), (1, 8, blocks + resident - 1)]
    # Around the two calls: the views of the operands (transposes XLA answers with a layout) and of the
    # results; nothing repeats k and v, and nothing sums a group's dk and dv after the backward.
    outside = _primitives_outside_the_calls(traced)
    loss_own = {"convert_element_type", "reduce_sum", "broadcast_in_dim"}
    assert set(outside) <= {"transpose", "reshape", "pallas_call", "name"} | loss_own
    last = len(outside) - 1 - outside[::-1].index("pallas_call")
    assert outside.count("pallas_call") == 2 and set(outside[last + 1:]) == {"reshape", "transpose"}  # views of dq, dk, dv
    # A full layer of the same model (no window): no record of a band.
    clear_dispatch_log()
    jax.eval_shape(lambda q, k, v: dot_product_attention(q, k, v, causal=True, backend="pallas"), spec(48), spec(8), spec(8))
    (record,) = snapshot_dispatch_log()
    assert "band" not in record and "kv_blocks_grid" not in record


def test_the_resident_pair_keeps_the_residuals_names():
    """``flash_out`` and ``flash_lse``, as the causal kernels name theirs: a
    remat policy that saves them runs no second forward in the backward pass."""
    q, k, v = _qkv(512, 6, 2, 128)
    loss = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, window=200, block_q=128, block_kv=128))
    policy = jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    text = _program(jax.grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1, 2)), q, k, v)
    assert text.count("pallas_call") == 2  # the forward and the backward
    everything = _program(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2)), q, k, v)
    assert everything.count("pallas_call") == 3  # without the names the forward runs again


@pytest.mark.parametrize("block_q,block_kv,window", [(256, 128, 130), (128, 256, 257), (256, 256, 64)])
def test_banded_kernels_in_place_with_unequal_blocks(block_q, block_kv, window):
    q, k, v = _qkv(512, 2, 2, 128, seed=block_q + window)
    _assert_matches_dense(q, k, v, window, block_q=block_q, block_kv=block_kv, block_b=1)


@pytest.fixture(params=["one_kernel", "two_kernels"])
def backward_form(request, monkeypatch):
    if request.param == "two_kernels":
        monkeypatch.setattr(flmod, "ONE_KERNEL_VMEM_BUDGET", 0)
    return request.param


# The head-major layout: a head narrower than a lane tile, a ragged length,
# several slices a cell; each backward form.
@pytest.mark.parametrize(
    "length,heads,kv_heads,dim,window,block",
    [
        (300, 6, 1, 32, 128, 128),  # boundary; padded rows and columns
        (300, 9, 1, 32, 77, 128),  # shorter than a block
        (320, 4, 4, 64, 200, 64),  # small blocks: most cells skipped behind the window
        (256, 2, 1, 40, 255, 128),
    ],
)
def test_banded_kernels_head_major_match_the_dense_path(backward_form, length, heads, kv_heads, dim, window, block):
    q, k, v = _qkv(length, heads, kv_heads, dim, seed=window, batch=2)
    blocks = dict(block_q=block, block_kv=block)
    assert flmod.layout_form(length, length, dim, dim, batch_heads=2 * heads, itemsize=4, **blocks) == "head_major"
    assert flmod.backward_form(length, length, dim, dim, batch_heads=2 * heads, itemsize=4, **blocks) == backward_form
    _assert_matches_dense(q, k, v, window, **blocks)


def test_a_window_no_shorter_than_the_sequence_is_the_causal_program():
    q, k, v = _qkv(256, 2, 1, 128)
    blocks = dict(block_q=128, block_kv=128, block_b=1)

    def program(window):
        fn = lambda q, k, v: _loss_and_grads(flash_attention, q, k, v, causal=True, window=window, **blocks)
        return str(jax.make_jaxpr(fn)(q, k, v))

    causal = program(None)
    assert "window" not in causal and "cases" not in causal  # the kernels are told nothing new
    assert "_band" not in causal
    of_before = lambda q, k, v: _loss_and_grads(
        lambda q, k, v: flmod._flash(q, k, v, None, 128 ** -0.5, 128, 128, None, True, 1, None), q, k, v)
    assert str(jax.make_jaxpr(of_before)(q, k, v)) == causal
    assert program(256) == causal and program(4096) == causal
    assert program(255) != causal
    _assert_matches_dense(q, k, v, 256, **blocks)


def test_without_a_window_the_grid_and_the_maps_are_the_causal_ones():
    # The helpers the index maps are built from, at a window of None: the
    # expressions of before (min with the diagonal's block; max with the kv
    # block's first q block), every cell of a 4 x 4 grid.
    for qi in range(4):
        for ki in range(4):
            assert int(flmod._visible_kv_block(qi, ki, 128, 128, None)) == min(ki, qi)
            assert int(flmod._visible_q_block(ki, qi, 128, 128, 4, None)) == max(qi, ki)
            assert flmod._causal_blocks(qi, ki, 128, 128) == (ki <= qi, ki == qi, False)
    assert flmod._band_statics(4, 4, 128, 128, None) == {}
    assert flmod._first_kv_block(3, 128, 128, None) == 0
    counts = flmod.band_blocks(8, 8, 512, 512, None)
    assert counts == {"visited": 36, "causal": 36, "cases": ((False, False), (True, False))}


def test_band_blocks_counts_the_cells_with_work():
    # The cell's window layers: 4,096 positions, 512-row blocks, window 512: a
    # q block visits its own kv block and the one before it.
    counts = flmod.band_blocks(8, 8, 512, 512, 512)
    assert (counts["visited"], counts["causal"]) == (15, 36)
    assert counts["cases"] == ((False, True), (True, False))  # no block meets both edges, none is unmasked
    assert flmod.visited_blocks(4096, 4096, block_q=512, block_kv=512, window=512) == {
        "kv_blocks_visited": 15, "kv_blocks_causal": 36,
    }
    assert flmod.visited_blocks(4096, 4096, block_q=256, block_kv=256, window=512)["kv_blocks_visited"] == 45
    # Every visited cell holds a visible pair and no skipped cell does.
    for block_q, block_kv, window in [(128, 128, 100), (256, 128, 130), (128, 256, 257), (64, 64, 200)]:
        n_q, n_kv = 512 // block_q, 512 // block_kv
        mask = np.asarray(causal_mask(512, 512, window)).reshape(n_q, block_q, n_kv, block_kv)
        has_work, full = mask.any(axis=(1, 3)), mask.all(axis=(1, 3))
        for qi in range(n_q):
            for ki in range(n_kv):
                visible, diagonal, far = flmod._causal_blocks(qi, ki, block_q, block_kv, window)
                assert visible == has_work[qi, ki]
                assert (diagonal or far) == (visible and not full[qi, ki])
                if visible:
                    assert int(flmod._first_kv_block(qi, block_q, block_kv, window)) <= ki
                    assert int(flmod._last_q_block(ki, block_q, block_kv, n_q, window)) >= qi
        assert flmod.band_blocks(n_q, n_kv, block_q, block_kv, window)["visited"] == has_work.sum()


def test_skipped_cells_name_a_neighbours_block_at_both_edges():
    """Forward: a q block's cells before its first visible kv block name that
    block, those past the diagonal the diagonal's. dk/dv sweep: likewise for
    the q blocks of a kv block. In place, the output block a kv block brings
    in for ``delta`` is met exactly where the q block is first seen."""
    block, window, n = 128, 200, 6
    for qi in range(n):
        first = int(flmod._first_kv_block(qi, block, block, window))
        named = [int(flmod._visible_kv_block(qi, ki, block, block, window)) for ki in range(n)]
        assert named == [min(max(ki, first), qi) for ki in range(n)]
        assert len(set(named)) == qi - first + 1  # fetched: the visited blocks and no other
    for ki in range(n):
        last = int(flmod._last_q_block(ki, block, block, n, window))
        named = [int(flmod._visible_q_block(ki, qi, block, block, n, window)) for qi in range(n)]
        assert named == [max(min(qi, last), ki) for qi in range(n)]


def test_dispatcher_takes_a_window_and_logs_it(monkeypatch, tmp_path):
    q, k, v = _qkv(768, 6, 1, 128)
    clear_dispatch_log()
    want = xla_attention(q, k, v, causal=True, window=100, logits_dtype=jnp.float32)
    got = dot_product_attention(q, k, v, causal=True, window=100, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)
    dense = dot_product_attention(q, k, v, causal=True, window=100, backend="xla", logits_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(want), atol=1e-6)
    dot_product_attention(q, k, v, causal=True, backend="pallas")
    dot_product_attention(q, k, v, causal=True, window=4096, backend="pallas")  # the causal mask: no new record
    log = snapshot_dispatch_log()
    banded = [r for r in log if r.get("window") == 100 and r["backend"] == "pallas"]
    causal = [r for r in log if "window" not in r and r["backend"] == "pallas"]
    assert len(banded) == 1 and len(causal) == 1  # one shape, two records
    assert banded[0]["kv_blocks_visited"] < banded[0]["kv_blocks_causal"]
    assert "kv_blocks_visited" not in causal[0] and "band" not in causal[0]
    # 768 positions in 256-row blocks, heads of 128: the resident pair holds the group of six in a cell.
    assert (banded[0]["band"], banded[0]["grouped_kv"], causal[0]["grouped_kv"]) == ("resident", "in_cell", "repeated")

    # The tune cache: a banded core reads .causal.window<W> entries and never
    # a causal entry's blocks.
    assert attn_tuning.shape_key("*", 4096, 4096, 72, 128, "bfloat16", True, None, 512).endswith(
        ".H72.D128.bfloat16.causal.window512"
    )
    cache = tmp_path / "cache.json"
    entry = {"backend": "pallas", "block_q": 1024, "block_kv": 1024, "block_b": 1}
    banded_entry = dict(entry, block_q=256, block_kv=512)
    attn_tuning.write_cache(str(cache), {
        attn_tuning.shape_key("*", 4096, 4096, 72, 128, "bfloat16", True): entry,
        attn_tuning.shape_key("*", 4096, 4096, 48, 128, "bfloat16", True, None, 512): banded_entry,
    })
    monkeypatch.setattr(attn_tuning, "_cache_path_override", str(cache))
    resolve = lambda heads, window: resolve_attention_backend(
        1, 4096, 4096, heads, 128, dtype="bfloat16", causal=True, window=window, on_tpu=True
    )
    assert resolve(72, None).block_config == {"block_q": 1024, "block_kv": 1024, "block_b": 1}
    assert resolve(72, 512).backend == "pallas" and resolve(72, 512).block_config is None  # the rule's default blocks
    assert resolve(48, 512).block_config == {"block_q": 256, "block_kv": 512, "block_b": 1}
    assert resolve(48, None).block_config is None
