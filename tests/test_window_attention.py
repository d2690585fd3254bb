"""The band in the flash kernels (``window`` beside ``causal``): forward and
all three gradients against the dense path in the interpreter, in each layout
and backward form, with the window's far edge inside a block, on a block
boundary, shorter than a block and no shorter than the sequence; and that a
call without a window builds what it built before."""

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


# The in-place layout: heads of whole lane tiles, sequences of whole blocks,
# one slice a cell, groups of 6 and 9 found through the index maps.
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
def test_banded_kernels_in_place_match_the_dense_path(heads, window):
    q, k, v = _qkv(512, heads, 1, 128, seed=window)
    blocks = dict(block_q=128, block_kv=128, block_b=1)
    assert flmod.layout_form(512, 512, 128, 128, batch_heads=heads, itemsize=4, **blocks) == "in_place"
    _assert_matches_dense(q, k, v, window, **blocks)


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
    assert "kv_blocks_visited" not in causal[0]
    assert banded[0]["grouped_kv"] == causal[0]["grouped_kv"]

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
