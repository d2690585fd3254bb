"""The three-way ``auto`` attention dispatch and its tuning cache
(ISSUE 6): test-pinned thresholds on both sides of the dense-logits HBM
budget and the single-block VMEM band, evidence-gated fused promotion via
the attn_tune cache, the 4-D input error path, and the trace-time
dispatch log bench.py stamps into its JSON line / run manifest."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sav_tpu.ops import attention as att
from sav_tpu.ops import attn_tuning
from sav_tpu.ops.attention import (
    _AUTO_PALLAS_LOGITS_BYTES,
    dot_product_attention,
    resolve_attention_backend,
)
from sav_tpu.ops.fused_attention import fused_eligible


@pytest.fixture(autouse=True)
def _isolate_cache(tmp_path):
    """Each test sees an EMPTY tune cache unless it installs one — the
    checked-in default table must not leak measured entries into the
    threshold assertions."""
    empty = tmp_path / "empty_cache.json"
    empty.write_text(json.dumps({"version": 1, "entries": {}}))
    attn_tuning.set_cache_path(str(empty))
    yield
    attn_tuning.set_cache_path(None)


def _install_cache(tmp_path, entries, infeasible=None):
    path = tmp_path / "cache.json"
    attn_tuning.write_cache(str(path), entries, infeasible)
    attn_tuning.set_cache_path(str(path))
    return str(path)


# ------------------------------------------------ threshold boundaries


def test_auto_dense_logits_budget_both_sides():
    """The pallas band boundary: 3 copies × 4 bytes × B·H·Lq·Lk against
    the 2 GiB budget, pinned one shape on each side."""
    # B=8, H=6, L=4096: 3*4*8*6*4096^2 = 9.66e9 > 2 GiB -> pallas
    over = resolve_attention_backend(8, 4096, 4096, 6, 64, on_tpu=True)
    assert over.backend == "pallas" and over.source == "threshold"
    # B=8, H=6, L=1024: 3*4*8*6*1024^2 = 0.6 GiB <= 2 GiB -> not pallas
    under = resolve_attention_backend(8, 1024, 1024, 6, 64, on_tpu=True)
    assert under.backend == "xla"
    # The exact constant is load-bearing for both assertions above.
    assert _AUTO_PALLAS_LOGITS_BYTES == 2 << 30


def test_auto_short_band_defaults_to_xla_without_measured_win():
    """Evidence-gated promotion: an eligible short shape with NO measured
    cache entry stays on XLA (the PERF.md §5 winner), with the reason
    naming the gate."""
    assert fused_eligible(197, 197, 64)
    d = resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=True)
    assert d.backend == "xla" and d.source == "default"
    assert "promotion" in d.reason


def test_auto_single_block_vmem_threshold_both_sides(tmp_path):
    """A fused cache entry only promotes INSIDE the single-block band:
    the same 'fused' verdict at an over-budget shape is ignored."""
    entries = {
        attn_tuning.shape_key("*", 197, 197, 6, 64): {
            "backend": "fused", "block_q": 256, "block_kv": None,
            "block_b": 4, "fwd_ms": 1.0, "fwd_bwd_ms": 3.0, "source": "t"},
        attn_tuning.shape_key("*", 2048, 2048, 6, 64): {
            "backend": "fused", "block_q": 256, "block_kv": None,
            "block_b": 1, "fwd_ms": 1.0, "fwd_bwd_ms": 3.0, "source": "t"},
    }
    _install_cache(tmp_path, entries)
    inside = resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=True)
    assert inside.backend == "fused" and inside.source == "tuned"
    assert inside.block_config == {"block_q": 256, "block_b": 4}
    assert not fused_eligible(2048, 2048, 64)
    outside = resolve_attention_backend(4, 2048, 2048, 6, 64, on_tpu=True)
    assert outside.backend == "xla"  # entry ignored: over the VMEM band


def test_auto_off_tpu_and_dropout_stay_xla():
    d = resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=False)
    assert d.backend == "xla" and "non-TPU" in d.reason
    d = resolve_attention_backend(
        256, 197, 197, 6, 64, on_tpu=True, kernels_ok=False
    )
    assert d.backend == "xla" and "ineligible" in d.reason


def test_tuned_pallas_entry_dispatches_below_threshold(tmp_path):
    """The autotuner sweeps all three backends — a measured pallas win in
    the sub-2-GiB band must dispatch (with its block config), not fall
    through to the XLA default."""
    _install_cache(tmp_path, {
        attn_tuning.shape_key("*", 785, 785, 6, 64): {
            "backend": "pallas", "block_q": 256, "block_kv": 256,
            "block_b": 2, "fwd_ms": 9.0, "fwd_bwd_ms": 12.0, "source": "t"},
    })
    # B=16 keeps dense logits (3·4·16·6·785² ≈ 0.7 GiB) under the 2 GiB
    # threshold — the entry, not the long-band rule, must pick pallas.
    d = resolve_attention_backend(16, 785, 785, 6, 64, on_tpu=True)
    assert d.backend == "pallas" and d.source == "tuned"
    assert d.block_config == {"block_q": 256, "block_kv": 256, "block_b": 2}


def test_tuned_xla_entry_reports_tuned_source(tmp_path):
    _install_cache(tmp_path, {
        attn_tuning.shape_key("*", 197, 197, 6, 64): {
            "backend": "xla", "block_q": None, "block_kv": None,
            "block_b": None, "fwd_ms": 2.25, "fwd_bwd_ms": 7.38,
            "source": "PERF"},
    })
    d = resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=True)
    assert d.backend == "xla" and d.source == "tuned"


def test_checked_in_default_cache_is_loadable_and_consulted():
    """The shipped table (sav_tpu/ops/attn_tune_cache.json) parses and
    resolves the DeiT-S shape to the fused win measured on the chip
    (PERF.md §6, PR 25), with that run named as its source."""
    attn_tuning.set_cache_path(None)  # default resolution
    assert os.path.exists(attn_tuning.DEFAULT_CACHE_PATH)
    cache = attn_tuning.load_cache(attn_tuning.DEFAULT_CACHE_PATH)
    assert cache.get("version") == attn_tuning.CACHE_VERSION
    d = resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=True)
    assert d.backend == "fused" and d.source == "tuned"
    assert "PR 25" in d.reason
    # The recorded Mosaic infeasibilities (block_b 16/32) survive too.
    inf = cache.get("infeasible", {})
    assert any(
        rec.get("block_b") in (16, 32)
        for recs in inf.values()
        for rec in recs
    )


@pytest.mark.parametrize(
    "shape, backend, source",
    [
        # (batch, q_len, kv_len, heads, dim): what PR 25 measured on the chip
        ((256, 197, 197, 6, 64), "fused", "tuned"),  # DeiT-S, cell 1
        ((128, 197, 197, 12, 64), "fused", "tuned"),  # ViT-B, cell 3
        ((128, 197, 197, 6, 64), "fused", "tuned"),  # the smallest batch measured winning
        # below it XLA keeps the dense tensors on chip and wins (measured):
        ((96, 197, 197, 6, 64), "xla", "default"),
        ((64, 197, 197, 12, 64), "xla", "default"),
        ((8, 197, 197, 12, 64), "xla", "default"),  # a serve bucket
        # and what it did not: no cell runs these, they stay dense
        ((16, 785, 785, 6, 64), "xla", "tuned"),  # TNT outer keeps its entry
        ((256, 1, 197, 6, 64), "xla", "default"),  # class attention
        ((256, 196, 49, 6, 64), "xla", "default"),  # CvT: Lq != Lkv
        ((256, 197, 197, 3, 64), "xla", "default"),  # DeiT-Ti: not measured
        ((256, 197, 197, 4, 48), "xla", "default"),  # CaiT-XXS width
        ((3136, 17, 17, 4, 6), "xla", "default"),  # TNT inner
    ],
)
def test_checked_in_cache_promotes_only_what_was_measured(shape, backend, source):
    attn_tuning.set_cache_path(None)
    d = resolve_attention_backend(*shape, on_tpu=True)
    assert (d.backend, d.source) == (backend, source)
    # Off the TPU nothing is promoted, whatever the table says.
    assert resolve_attention_backend(*shape, on_tpu=False).backend == "xla"


# What the checked-in table holds for the latent attention core (a query/key
# head of 192 beside a value head of 128): tools/attn_tune.py on the chip.
LATENT_BLOCKS = {"block_q": 1024, "block_kv": 1024, "block_b": 1}


def test_the_latent_cores_entry_is_keyed_by_both_head_sizes_and_dispatches_flash():
    attn_tuning.set_cache_path(None)
    key = attn_tuning.shape_key("*", 4096, 4096, 32, 192, "bfloat16", True, 128)
    assert key == "B*.Lq4096.Lkv4096.H32.D192v128.bfloat16.causal"
    assert attn_tuning.shape_key(2, 4096, 4096, 16, 128, "bfloat16", True, 128).endswith(".H16.D128.bfloat16.causal")
    entry = attn_tuning.lookup(2, 4096, 4096, 32, 192, causal=True, value_dim=128)
    assert entry["backend"] == "pallas" and entry["fwd_bwd_ms"] > entry["fwd_ms"] > 0
    cell = resolve_attention_backend(2, 4096, 4096, 32, 192, on_tpu=True, causal=True, value_dim=128)
    assert cell.backend == "pallas" and cell.block_config == LATENT_BLOCKS
    # The entry is this core's alone: without the mask, at one head size, or
    # at the other family's heads it is not read.
    assert attn_tuning.lookup(2, 4096, 4096, 32, 192, causal=False, value_dim=128) is None
    assert attn_tuning.lookup(2, 4096, 4096, 32, 192, causal=True) is None
    assert attn_tuning.lookup(2, 4096, 4096, 16, 192, causal=True, value_dim=128) is None
    assert resolve_attention_backend(2, 4096, 4096, 32, 192, on_tpu=False, causal=True, value_dim=128).backend == "xla"
    # Two head sizes never resolve to the single-pass kernel, whatever a table says.
    short = resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=True, value_dim=32)
    assert short.backend == "xla"


@pytest.mark.parametrize("shape, causal, backend, blocks", [
    ((256, 197, 197, 6, 64), False, "fused", {"block_b": 1}),  # deit_s.train_resident, deit_s.train_dp4's chip
    ((128, 197, 197, 12, 64), False, "fused", {"block_b": 2}),  # vit_b.train_resident
    ((2, 4096, 4096, 16, 128), True, "pallas", {"block_q": 1024, "block_kv": 1024, "block_b": 1}),  # ouro
])
def test_auto_at_the_accepted_cells_shapes_resolves_as_before(shape, causal, backend, blocks):
    attn_tuning.set_cache_path(None)
    for value_dim in (None, shape[-1]):  # a value head of the query's size is no new key
        d = resolve_attention_backend(*shape, on_tpu=True, causal=causal, value_dim=value_dim)
        assert d.backend == backend and d.block_config == blocks


@pytest.mark.parametrize("num_devices", [2, 4, 8])
def test_partitioned_program_resolves_as_before_the_fused_entries(num_devices):
    """A Mosaic call cannot be partitioned automatically: in a program over
    more than one device ``auto`` answers what it answered before PR 25's
    cache entries, so a working multi-chip run is not turned into a
    lowering failure."""
    attn_tuning.set_cache_path(None)
    for shape in ((256, 197, 197, 6, 64), (128, 197, 197, 12, 64)):
        by_argument = resolve_attention_backend(*shape, on_tpu=True, num_devices=num_devices)
        with att.partitioned_over(num_devices):
            in_context = resolve_attention_backend(*shape, on_tpu=True)
            # The enclosing trace's word stands until the block ends, and a
            # nested single-device trace has its own.
            with att.partitioned_over(1):
                assert resolve_attention_backend(*shape, on_tpu=True).backend == "fused"
        assert by_argument == in_context
        assert in_context.backend == "xla" and in_context.block_config is None
    assert resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=True).backend == "fused"
    with att.partitioned_over(num_devices):
        # What the bands and the other entries said, they still say: the
        # recipe's global batch goes to the flash kernel (and an explicit
        # backend is the caller's own business).
        assert resolve_attention_backend(1024, 197, 197, 6, 64, on_tpu=True).backend == "pallas"
        assert resolve_attention_backend(16, 785, 785, 6, 64, on_tpu=True).source == "tuned"
        assert resolve_attention_backend(
            256, 197, 197, 6, 64, on_tpu=True, requested="fused").backend == "fused"


@pytest.mark.parametrize("mesh_devices, backend", [(1, "fused"), (2, "xla"), (4, "xla")])
def test_trainer_traces_its_step_knowing_its_mesh(monkeypatch, devices, mesh_devices, backend):
    """The trainer's own trace: DeiT-S's attention shape on a TPU resolves
    to the kernel on a mesh of one device and to the dense path on a
    data-parallel mesh (traced only: nothing is compiled or run)."""
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    attn_tuning.set_cache_path(None)
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    batch = 128  # the checked-in entry holds from batch 128 up
    config = TrainConfig(
        model_name="deit_s_patch16", num_classes=10, image_size=224,
        compute_dtype="bfloat16", global_batch_size=batch, transpose_images=False,
        model_overrides={"num_layers": 1}, seed=0,
    )
    trainer = Trainer(config, mesh=create_mesh({"data": mesh_devices}, devices=devices[:mesh_devices]))
    state = jax.eval_shape(trainer.init_state)
    images = jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((batch,), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    att.clear_dispatch_log()
    trainer._train_step.lower(state, {"images": images, "labels": labels}, rng)
    trainer._eval_step.lower(state, {"images": images, "labels": labels})
    log = att.snapshot_dispatch_log()
    att.clear_dispatch_log()
    assert [(e["shape"], e["backend"]) for e in log] == [([batch, 197, 6, 64], backend)]


def test_wildcard_entry_holds_from_its_smallest_measured_batch(tmp_path):
    key_star = attn_tuning.shape_key("*", 197, 197, 6, 64)
    key_exact = attn_tuning.shape_key(16, 197, 197, 6, 64)
    _install_cache(tmp_path, {
        key_star: {"backend": "fused", "min_batch": 128, "source": "star"},
        key_exact: {"backend": "fused", "min_batch": 128, "source": "exact"},
    })
    assert attn_tuning.lookup(128, 197, 197, 6, 64)["source"] == "star"
    assert attn_tuning.lookup(127, 197, 197, 6, 64) is None
    # An entry for the exact batch is that batch's own measurement.
    assert attn_tuning.lookup(16, 197, 197, 6, 64)["source"] == "exact"
    assert resolve_attention_backend(64, 197, 197, 6, 64, on_tpu=True).source == "default"


def test_lookup_batch_wildcard_and_exact_precedence(tmp_path):
    key_star = attn_tuning.shape_key("*", 197, 197, 6, 64)
    key_exact = attn_tuning.shape_key(256, 197, 197, 6, 64)
    _install_cache(tmp_path, {
        key_star: {"backend": "xla", "source": "star"},
        key_exact: {"backend": "fused", "block_q": 128, "source": "exact"},
    })
    assert attn_tuning.lookup(256, 197, 197, 6, 64)["source"] == "exact"
    assert attn_tuning.lookup(64, 197, 197, 6, 64)["source"] == "star"
    assert attn_tuning.lookup(64, 198, 198, 6, 64) is None


def test_broken_cache_degrades_to_static_rule(tmp_path):
    path = tmp_path / "broken.json"
    path.write_text("{not json")
    attn_tuning.set_cache_path(str(path))
    d = resolve_attention_backend(256, 197, 197, 6, 64, on_tpu=True)
    assert d.backend == "xla" and d.source == "default"


# ------------------------------------------------ dot_product_attention


def _qkv(b=2, l=60, h=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(kk, (b, l, h, d)) for kk in ks)


def test_dispatch_fused_backend_matches_xla():
    q, k, v = _qkv()
    out = dot_product_attention(q, k, v, backend="fused")
    ref = dot_product_attention(q, k, v, backend="xla")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_dispatch_auto_picks_fused_from_cache(tmp_path, monkeypatch):
    """End to end: a measured fused entry + simulated TPU backend routes
    the real call through the fused kernel."""
    q, k, v = _qkv(l=50)
    _install_cache(tmp_path, {
        attn_tuning.shape_key("*", 50, 50, 2, 16, q.dtype): {
            "backend": "fused", "block_q": 64, "block_kv": None,
            "block_b": 1, "source": "t"},
    })
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    called = {}
    real = att._fused.fused_attention

    def spy(*a, **kw):
        called.update(kw)
        called["hit"] = True
        return real(*a, **kw, interpret=True)

    monkeypatch.setattr(att._fused, "fused_attention", spy)
    out = dot_product_attention(q, k, v, backend="auto")
    assert called.get("hit") and called.get("block_q") == 64
    ref = att.xla_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_dispatch_4d_error_paths():
    """The kernel backends demand 4-D [B, L, H, D]; dropout likewise
    forces the XLA path — both raise rather than silently degrade."""
    x3 = jnp.zeros((4, 8, 8))
    for backend in ("pallas", "fused"):
        with pytest.raises(ValueError, match="4-D"):
            dot_product_attention(x3, x3, x3, backend=backend)
    q, k, v = _qkv(l=16)
    with pytest.raises(ValueError, match="4-D"):
        dot_product_attention(
            q, k, v, backend="fused",
            dropout_rate=0.5, deterministic=False,
            dropout_rng=jax.random.PRNGKey(0),
        )
    # 5-D (an un-flattened TNT inner layout) is kernel-ineligible too.
    x5 = jnp.zeros((2, 3, 8, 2, 8))
    with pytest.raises(ValueError, match="4-D"):
        dot_product_attention(x5, x5, x5, backend="fused")


def test_dispatch_rejects_unknown_backend():
    q, k, v = _qkv(l=8)
    with pytest.raises(ValueError, match="unknown attention backend"):
        dot_product_attention(q, k, v, backend="cuda")


def test_dispatch_log_records_resolutions():
    att.clear_dispatch_log()
    q, k, v = _qkv(l=24)
    dot_product_attention(q, k, v, backend="xla")
    dot_product_attention(q, k, v, backend="fused")
    log = att.snapshot_dispatch_log()
    assert {e["backend"] for e in log} == {"xla", "fused"}
    for e in log:
        assert e["shape"] == [2, 24, 2, 16]
        assert e["kv_len"] == 24
        assert set(e) >= {"requested", "backend", "reason", "source"}
    # Idempotent per (shape, kv_len, requested): re-tracing adds no dupes.
    dot_product_attention(q, k, v, backend="xla")
    assert len(att.snapshot_dispatch_log()) == len(log)
    # Cross-attention with the same query shape but different kv_len is a
    # DISTINCT record (class-attention / CvT sites must not collapse).
    k2 = jnp.concatenate([k, k], axis=1)
    dot_product_attention(q, k2, k2, backend="xla")
    log2 = att.snapshot_dispatch_log()
    assert len(log2) == len(log) + 1
    assert {e["kv_len"] for e in log2} == {24, 48}
    att.clear_dispatch_log()
    assert att.snapshot_dispatch_log() == []


def test_attention_block_fused_backend():
    """Model plumbing: AttentionBlock(backend='fused') runs end to end and
    matches the XLA block bit-for-bit in structure (same params)."""
    from sav_tpu.models.layers.attention import SelfAttentionBlock

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 50, 32))
    fused_block = SelfAttentionBlock(num_heads=2, backend="fused")
    xla_block = SelfAttentionBlock(num_heads=2, backend="xla")
    variables = fused_block.init(jax.random.PRNGKey(1), x, is_training=False)
    out_f = fused_block.apply(variables, x, is_training=False)
    out_x = xla_block.apply(variables, x, is_training=False)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_x), atol=2e-5, rtol=2e-5
    )
