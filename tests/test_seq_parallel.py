"""Config-reachable sequence parallelism (VERDICT r3 item 5).

Covers the model seam :mod:`sav_tpu.parallel.seq_parallel` (pad-and-mask
routing into ring/Ulysses), the ``AttentionBlock(seq_parallel=...)`` wiring,
and the TrainConfig path — numerics pinned against the unsharded dense core
on the 8-device CPU mesh, including CLS-odd sequence lengths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sav_tpu.models import create_model
from sav_tpu.ops.attention import xla_attention
from sav_tpu.parallel import create_mesh, sequence_parallel_attention
from sav_tpu.train import TrainConfig, Trainer


def _qkv(b=2, l=17, h=4, d=8, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, l, h, d), dtype) for k in ks)


def _dense_talking_heads(q, k, v, w_pre, w_post, scale=None):
    """Dense reference for the ring talking-heads path (the math of
    models.layers.attention.talking_heads_attention, without the modules)."""
    scale = scale or q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("hi,bhqk->biqk", w_pre.astype(jnp.float32), s)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.einsum("hi,bhqk->biqk", w_post.astype(jnp.float32), p)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@pytest.mark.parametrize("length", [16, 17])  # divisible and CLS-odd (pad)
def test_ring_talking_heads_matches_dense(devices, length):
    """The head-pair-accumulator ring equals the dense pre/post-mix core,
    including the pad-and-mask path."""
    mesh = create_mesh({"data": 4, "seq": 2})
    q, k, v = _qkv(l=length)
    wk = jax.random.split(jax.random.PRNGKey(7), 2)
    w_pre = jax.random.normal(wk[0], (4, 4), jnp.float32)
    w_post = jax.random.normal(wk[1], (4, 4), jnp.float32)
    want = np.asarray(_dense_talking_heads(q, k, v, w_pre, w_post), np.float32)
    got = np.asarray(
        sequence_parallel_attention(
            q, k, v, mesh=mesh, method="ring", talking_heads=(w_pre, w_post)
        ),
        np.float32,
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_talking_heads_grads_match_dense(devices):
    """Gradients through the ring-TH path — q/k/v AND the mixing matrices
    (the CaiT trunk trains through this seam)."""
    mesh = create_mesh({"data": 4, "seq": 2})
    q, k, v = _qkv(l=17)
    wk = jax.random.split(jax.random.PRNGKey(8), 2)
    w_pre = jax.random.normal(wk[0], (4, 4), jnp.float32)
    w_post = jax.random.normal(wk[1], (4, 4), jnp.float32)

    def dense_loss(q, k, v, wp, wq):
        return jnp.mean(_dense_talking_heads(q, k, v, wp, wq) ** 2)

    def sp_loss(q, k, v, wp, wq):
        return jnp.mean(
            sequence_parallel_attention(
                q, k, v, mesh=mesh, method="ring", talking_heads=(wp, wq)
            ) ** 2
        )

    want = jax.grad(dense_loss, argnums=(0, 1, 2, 3, 4))(q, k, v, w_pre, w_post)
    got = jax.grad(sp_loss, argnums=(0, 1, 2, 3, 4))(q, k, v, w_pre, w_post)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=5e-5, rtol=5e-5,
        )


def test_talking_heads_rejects_ulysses(devices):
    mesh = create_mesh({"data": 4, "seq": 2})
    q, k, v = _qkv()
    w = jnp.eye(4)
    with pytest.raises(ValueError, match="ring-only"):
        sequence_parallel_attention(
            q, k, v, mesh=mesh, method="ulysses", talking_heads=(w, w)
        )


@pytest.mark.parametrize("method", ["ring", "ulysses"])
@pytest.mark.parametrize("length", [16, 17])  # divisible and CLS-odd (pad)
@pytest.mark.slow
def test_wrapper_matches_dense(devices, method, length):
    mesh = create_mesh({"data": 4, "seq": 2})
    q, k, v = _qkv(l=length)
    want = np.asarray(xla_attention(q, k, v), np.float32)
    got = np.asarray(
        sequence_parallel_attention(q, k, v, mesh=mesh, method=method),
        np.float32,
    )
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


@pytest.mark.slow
def test_wrapper_grads_match_dense(devices):
    mesh = create_mesh({"data": 4, "seq": 2})
    q, k, v = _qkv(l=17)

    def dense_loss(q, k, v):
        return jnp.mean(xla_attention(q, k, v) ** 2)

    def sp_loss(q, k, v):
        return jnp.mean(
            sequence_parallel_attention(q, k, v, mesh=mesh, method="ring") ** 2
        )

    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(sp_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=5e-6, rtol=5e-6,
        )


def test_ulysses_rejects_indivisible_heads(devices):
    mesh = create_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(h=6)  # 6 % 4 != 0
    with pytest.raises(ValueError, match="divisible"):
        sequence_parallel_attention(q, k, v, mesh=mesh, method="ulysses")


@pytest.mark.slow
@pytest.mark.parametrize("name,method,kwargs", [
    # ViT: every block SP-routed; 32² p8 → 17 tokens exercises pad-and-mask
    # (the acceptance test VERDICT r3 item 5 names). Both methods.
    ("vit_ti_patch16", "ring",
     dict(num_layers=2, embed_dim=64, num_heads=4, patch_shape=(8, 8))),
    ("vit_ti_patch16", "ulysses",
     dict(num_layers=2, embed_dim=64, num_heads=4, patch_shape=(8, 8))),
    # TNT shards its outer patch-token stream only.
    ("tnt_s_patch16", "ring",
     dict(num_layers=2, embed_dim=64, inner_ch=12, num_heads=4,
          inner_num_heads=2, patch_shape=(8, 8))),
    # CeiT shards its trunk; the LCA head stays unsharded.
    ("ceit_t", "ring", dict(num_layers=2, embed_dim=64, num_heads=4)),
    # CaiT shards its talking-heads SA trunk (ring-only, head-pair
    # accumulators); the class-attention head stays unsharded.
    ("cait_xxs_24", "ring",
     dict(num_layers=2, num_layers_token_only=1, embed_dim=64, num_heads=4,
          patch_shape=(8, 8))),
])
def test_sp_model_forward_matches_unsharded(devices, name, method, kwargs):
    """A 2-way-SP forward equals the plain forward on the same params for
    every SP-capable family."""
    mesh = create_mesh({"data": 4, "seq": 2})
    dense = create_model(name, num_classes=10, **kwargs)
    sp = create_model(
        name, num_classes=10, seq_parallel=method, seq_mesh=mesh, **kwargs
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 32, 3), jnp.float32)
    variables = dense.init({"params": jax.random.PRNGKey(1)}, x, is_training=False)
    # Zero-init head makes fresh logits vacuously equal — randomize it.
    head = variables["params"]["head"]["kernel"]
    variables["params"]["head"]["kernel"] = jax.random.normal(
        jax.random.PRNGKey(2), head.shape, head.dtype
    )
    want = np.asarray(dense.apply(variables, x, is_training=False), np.float32)
    got = np.asarray(
        jax.jit(lambda v, x: sp.apply(v, x, is_training=False))(variables, x),
        np.float32,
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_sp_model_requires_mesh(devices):
    with pytest.raises(ValueError, match="seq_mesh"):
        m = create_model(
            "vit_ti_patch16", num_classes=10, num_layers=1, embed_dim=32,
            num_heads=2, patch_shape=(8, 8), seq_parallel="ring",
        )
        x = jnp.zeros((1, 16, 16, 3))
        m.init({"params": jax.random.PRNGKey(0)}, x, is_training=False)


def test_sp_rejects_attention_free_models(devices):
    with pytest.raises(ValueError, match="sequence parallelism"):
        create_model(
            "mixer_s_patch32", num_classes=10, seq_parallel="ring",
            seq_mesh=create_mesh({"data": 4, "seq": 2}),
        )


@pytest.mark.slow
def test_trainer_sp_train_step(devices):
    """TrainConfig.sequence_parallel drives a full train step on a
    (data × seq) mesh — the framework-level capability, not the bare op."""
    config = TrainConfig(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=8,
        num_train_images=32,
        num_epochs=2,
        warmup_epochs=1,
        base_lr=1e-3,
        transpose_images=False,
        mesh_axes={"data": 4, "seq": 2},
        sequence_parallel="ring",
        model_overrides=dict(num_layers=2, embed_dim=64, num_heads=4),
        seed=0,
    )
    trainer = Trainer(config)
    assert trainer.model.seq_parallel == "ring"
    batch = {
        "images": np.random.default_rng(0)
        .normal(size=(8, 32, 32, 3))
        .astype(np.float32),
        "labels": (np.arange(8) % 10).astype(np.int32),
    }
    state = trainer.init_state(0)
    state, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    em = trainer.eval_step(state, batch)
    assert np.isfinite(float(jax.device_get(em["loss_sum"])))


@pytest.mark.slow
def test_trainer_sp_composes_with_grad_accum(devices):
    """SP attention inside the microbatched grad-accum step: the shard_map
    runs under lax.scan's body — a distinct trace path from the plain
    step."""
    config = TrainConfig(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=8,
        num_train_images=32,
        num_epochs=2,
        warmup_epochs=1,
        base_lr=1e-3,
        grad_accum_steps=2,
        transpose_images=False,
        mesh_axes={"data": 4, "seq": 2},
        sequence_parallel="ring",
        model_overrides=dict(num_layers=2, embed_dim=64, num_heads=4),
        seed=0,
    )
    trainer = Trainer(config)
    batch = {
        "images": np.random.default_rng(0)
        .normal(size=(8, 32, 32, 3))
        .astype(np.float32),
        "labels": (np.arange(8) % 10).astype(np.int32),
    }
    state = trainer.init_state(0)
    state, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_trainer_sp_requires_seq_axis(devices):
    config = TrainConfig(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        global_batch_size=8,
        num_train_images=32,
        sequence_parallel="ring",
        transpose_images=False,
    )
    with pytest.raises(ValueError, match="'seq' mesh axis"):
        Trainer(config)


# ------------------------------------------------- replication observability


def test_replication_fallback_notifies_listeners(devices):
    """ISSUE 4 satellite: the batch-replication fallback routes through
    the observability hook — a registered listener (what Trainer.fit
    installs) receives the machine-readable event at trace time."""
    from sav_tpu.parallel import seq_parallel as sp

    mesh = create_mesh({"data": 4, "seq": 2})
    events = []
    unsubscribe = sp.on_batch_replication(events.append)
    try:
        q, k, v = _qkv(b=2, l=16)  # batch 2 does not divide data product 4
        sequence_parallel_attention(q, k, v, mesh=mesh, method="ring")
    finally:
        unsubscribe()
    assert events and events[0] == {"batch": 2, "data_axis_product": 4}
    # After unsubscribe the hook no longer reaches the listener.
    before = len(events)
    q, k, v = _qkv(b=2, l=16, seed=1)
    sequence_parallel_attention(q, k, v, mesh=mesh, method="ring")
    assert len(events) == before


def test_replication_warning_fires_once_per_shape_without_listeners():
    """Without listeners the module warns once per (batch, group) shape
    per process — not per call (the old per-trace UserWarning spam)."""
    import warnings

    from sav_tpu.parallel import seq_parallel as sp

    key = (313, 757)  # synthetic shape no other test uses
    sp._replication_warned.discard(key)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sp._replication_fallback(*key)
        sp._replication_fallback(*key)
    assert len(caught) == 1
    assert "replicating the batch" in str(caught[0].message)


def test_replication_listener_exceptions_are_swallowed():
    import warnings

    from sav_tpu.parallel import seq_parallel as sp

    def bad_listener(info):
        raise RuntimeError("observer crash")

    unsubscribe = sp.on_batch_replication(bad_listener)
    try:
        with warnings.catch_warnings():
            # A crashed listener counts as unhandled, so the module falls
            # back to its own (expected) warning — not the test's concern.
            warnings.simplefilter("ignore")
            sp._replication_fallback(311, 751)  # must not raise
    finally:
        unsubscribe()


def test_fit_records_replication_fallback_once(devices, tmp_path):
    """Trainer integration: a degraded-parallelism fit warns ONCE, marks
    the span trace, sets the ledger gauge, and notes the manifest. The
    trigger is the realistic one — grad accumulation shrinks the
    micro-batch (4/2 = 2) below the 4-way data-axis product while the
    global batch still places cleanly."""
    import json as _json
    import warnings

    from sav_tpu.obs.manifest import RunManifest

    config = TrainConfig(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=4,
        grad_accum_steps=2,  # micro-batch 2 does not divide data axis 4
        num_train_images=8,
        num_epochs=1,
        warmup_epochs=1,
        lr_scaling_divisor=4,
        transpose_images=False,
        log_every_steps=2,
        log_dir=str(tmp_path),
        trace_spans=True,
        mesh_axes={"data": 4, "seq": 2},
        sequence_parallel="ring",
        model_overrides=dict(num_layers=1, embed_dim=64, num_heads=4),
        seed=0,
    )
    trainer = Trainer(config)
    manifest = RunManifest(str(tmp_path / "manifest.json"), kind="train")
    manifest.begin()
    rng = np.random.default_rng(0)

    def batches(n):
        for _ in range(n):
            yield {
                "images": rng.normal(size=(4, 32, 32, 3)).astype(np.float32),
                "labels": (np.arange(4) % 10).astype(np.int32),
            }

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trainer.fit(batches(2), num_steps=2, manifest=manifest)
    fit_warnings = [
        w for w in caught
        if "batch-replication fallback" in str(w.message)
    ]
    assert len(fit_warnings) == 1  # once per fit, not per call/trace
    doc = RunManifest.load(manifest.path)
    assert doc["notes"]["seq_replication_fallback"] == {
        "batch": 2, "data_axis_product": 4,  # the micro-batch, not global
    }
    assert trainer.last_goodput["gauges"]["seq/replicated_batch"] == 2.0
    with open(tmp_path / "spans.trace.json") as f:
        names = {e["name"] for e in _json.load(f)["traceEvents"]}
    assert "sav:fit/seq_replication_fallback" in names
