"""Trainer integration tests on the 8-device virtual CPU mesh — the
train-step coverage tier the reference lacked (SURVEY.md §4)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sav_tpu.data import fake_data_iterator, synthetic_data_iterator
from sav_tpu.parallel import create_mesh
from sav_tpu.train import Checkpointer, TrainConfig, Trainer


def _smoke_config(**overrides):
    base = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=16,
        num_train_images=16 * 4,  # 4 steps/epoch
        num_epochs=2,
        warmup_epochs=1,
        base_lr=1e-3,
        lr_scaling_divisor=16,
        transpose_images=False,
        log_every_steps=2,
        eval_every_epochs=1,
        seed=0,
    )
    base.update(overrides)
    return TrainConfig(**base)


def _small_model_overrides():
    return dict(num_layers=2, embed_dim=64, num_heads=4)


def _trainer(config=None, **model_overrides):
    from sav_tpu.models import create_model

    config = config or _smoke_config()
    model = create_model(
        config.model_name,
        num_classes=config.num_classes,
        dtype=jnp.float32,
        **(_small_model_overrides() | model_overrides),
    )
    return Trainer(config, model=model)


@pytest.mark.slow
def test_loss_decreases_on_learnable_data(devices):
    trainer = _trainer()
    state = trainer.init_state()
    data = synthetic_data_iterator(
        batch_size=16, image_size=32, num_classes=10, seed=0
    )
    rng = jax.random.PRNGKey(0)
    losses = []
    for _, batch in zip(range(30), data):
        state, metrics = trainer.train_step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses
    assert int(jax.device_get(state.step)) == 30


def test_state_is_sharded_on_mesh(devices):
    trainer = _trainer()
    state = trainer.init_state()
    leaf = jax.tree.leaves(state.params)[0]
    assert len(leaf.sharding.device_set) == 8  # replicated over the full mesh


@pytest.mark.slow
def test_fit_loop_with_eval_and_transpose(devices):
    cfg = _smoke_config(transpose_images=True)
    trainer = _trainer(cfg)
    train_iter = synthetic_data_iterator(
        batch_size=16, image_size=32, num_classes=10, transpose=True
    )
    eval_fn = lambda: synthetic_data_iterator(
        batch_size=16, image_size=32, num_classes=10, transpose=True, num_batches=2
    )
    state, history = trainer.fit(
        train_iter, num_steps=8, eval_iter_fn=eval_fn
    )
    assert int(jax.device_get(state.step)) == 8
    assert any("eval_loss" in h for h in history)
    assert any("images_per_sec" in h for h in history)


@pytest.mark.slow
def test_batch_stats_model_trains(devices):
    """BatchNorm models thread batch_stats through the same trainer
    (collapses the reference's base.py/base_with_state.py split)."""
    from sav_tpu.models import create_model

    cfg = _smoke_config(model_name="botnet_t3", image_size=64)
    model = create_model(
        "botnet_t3", num_classes=10, dtype=jnp.float32, stage_sizes=(1, 1, 1, 1)
    )
    trainer = Trainer(cfg, model=model)
    state = trainer.init_state()
    assert state.batch_stats  # BN present
    before = jax.device_get(jax.tree.leaves(state.batch_stats)[0]).copy()
    data = synthetic_data_iterator(batch_size=16, image_size=64, num_classes=10)
    rng = jax.random.PRNGKey(0)
    for _, batch in zip(range(2), data):
        state, metrics = trainer.train_step(state, batch, rng)
    after = jax.device_get(jax.tree.leaves(state.batch_stats)[0])
    assert not np.allclose(before, after)  # running stats updated
    assert np.isfinite(float(metrics["loss"]))


def test_mixed_labels_loss(devices):
    trainer = _trainer()
    state = trainer.init_state()
    batch = next(synthetic_data_iterator(batch_size=16, image_size=32, num_classes=10))
    batch["mix_labels"] = np.roll(batch["labels"], 1)
    batch["ratio"] = np.full((16,), 0.7, np.float32)
    state, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["loss"]))


def test_fake_data_shapes():
    it = fake_data_iterator(batch_size=4, image_size=16, transpose=True)
    batch = next(it)
    assert batch["images"].shape == (16, 16, 3, 4)
    it = fake_data_iterator(batch_size=4, image_size=16)
    assert next(it)["images"].shape == (4, 16, 16, 3)


@pytest.mark.slow
def test_checkpoint_save_restore(tmp_path, devices):
    cfg = _smoke_config(checkpoint_dir=str(tmp_path / "ckpt"))
    trainer = _trainer(cfg)
    state = trainer.init_state()
    data = synthetic_data_iterator(batch_size=16, image_size=32, num_classes=10)
    rng = jax.random.PRNGKey(0)
    for _, batch in zip(range(3), data):
        state, _ = trainer.train_step(state, batch, rng)
    trainer.checkpointer.save(3, state)
    trainer.checkpointer.wait()

    # Fresh trainer restores the latest step into the right structure.
    trainer2 = _trainer(cfg)
    restored = trainer2.restore_or_init()
    assert int(jax.device_get(restored.step)) == 3
    a = jax.device_get(jax.tree.leaves(state.params)[0])
    b = jax.device_get(jax.tree.leaves(restored.params)[0])
    np.testing.assert_allclose(a, b)


@pytest.mark.slow
def test_fit_final_step_on_checkpoint_boundary(tmp_path, devices):
    """Final step landing exactly on an epoch-checkpoint boundary must not
    double-save (orbax raises StepAlreadyExistsError)."""
    cfg = _smoke_config(
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every_epochs=2
    )
    trainer = _trainer(cfg)
    train_iter = synthetic_data_iterator(batch_size=16, image_size=32, num_classes=10)
    state, _ = trainer.fit(train_iter, num_steps=8)  # 4 steps/epoch → epoch 2
    assert trainer.checkpointer.latest_step() == 8


def test_weight_decay_mask():
    from sav_tpu.train import weight_decay_mask

    params = {
        "block": {"kernel": jnp.zeros((4, 4)), "bias": jnp.zeros((4,))},
        "pos_embed": jnp.zeros((1, 5, 4)),
        "cls": jnp.zeros((1, 1, 4)),
    }
    mask = weight_decay_mask(params)
    assert mask["block"]["kernel"] is True
    assert mask["block"]["bias"] is False
    assert mask["pos_embed"] is False
    assert mask["cls"] is False


def test_schedule_shape():
    from sav_tpu.train import warmup_cosine_schedule

    sched = warmup_cosine_schedule(
        1e-3, steps_per_epoch=10, warmup_epochs=2, num_epochs=10, end_lr=1e-5
    )
    assert float(sched(0)) == 0.0
    assert abs(float(sched(20)) - 1e-3) < 1e-9  # peak at end of warmup
    assert float(sched(100)) <= 1e-4  # decayed


@pytest.mark.slow
def test_grad_accum_matches_full_batch(devices):
    """K micro-batches, averaged grads → same update as one full batch
    (deterministic model: no dropout/BN, rates are 0 by default)."""
    import dataclasses

    from sav_tpu.data import synthetic_data_iterator
    from sav_tpu.models import create_model
    from sav_tpu.train import TrainConfig, Trainer

    base = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=16,
        compute_dtype="float32", global_batch_size=16, num_train_images=64,
        num_epochs=2, warmup_epochs=1, transpose_images=False,
        label_smoothing=0.0, base_lr=0.01, seed=0,
    )
    model = create_model("vit_ti_patch16", num_classes=10, num_layers=2,
                         embed_dim=32, num_heads=2, patch_shape=(4, 4))
    batch = next(synthetic_data_iterator(batch_size=16, image_size=16,
                                         num_classes=10, seed=5))
    rng = jax.random.PRNGKey(0)
    results = {}
    for accum in (1, 4):
        cfg = dataclasses.replace(base, grad_accum_steps=accum)
        trainer = Trainer(cfg, model=model)
        state = trainer.init_state()
        state, metrics = trainer.train_step(state, batch, rng)
        results[accum] = (
            jax.device_get(state.params["head"]["kernel"]),
            float(jax.device_get(metrics["loss"])),
        )
    np.testing.assert_allclose(results[1][1], results[4][1], rtol=1e-5)
    np.testing.assert_allclose(results[1][0], results[4][0], rtol=1e-4, atol=1e-6)


def test_grad_accum_rejects_indivisible(devices):
    import dataclasses

    from sav_tpu.data import synthetic_data_iterator
    from sav_tpu.models import create_model
    from sav_tpu.train import TrainConfig, Trainer

    cfg = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=16,
        compute_dtype="float32", global_batch_size=16, num_train_images=64,
        num_epochs=2, warmup_epochs=1, transpose_images=False,
        grad_accum_steps=3, seed=0,
    )
    model = create_model("vit_ti_patch16", num_classes=10, num_layers=1,
                         embed_dim=32, num_heads=2, patch_shape=(4, 4))
    trainer = Trainer(cfg, model=model)
    state = trainer.init_state()
    batch = next(synthetic_data_iterator(batch_size=16, image_size=16, num_classes=10))
    with pytest.raises(ValueError, match="not divisible"):
        trainer.train_step(state, batch, jax.random.PRNGKey(0))


@pytest.mark.slow
def test_eval_pads_non_divisible_final_batch(devices):
    """50 eval examples in batches of 16 leave a remainder of 2 — not
    divisible by the 8-way data axis. evaluate() must pad + mask instead of
    crashing, and count exactly 50 examples."""
    trainer = _trainer()
    state = trainer.init_state()

    def eval_iter():
        rng = np.random.default_rng(0)
        remaining = 50
        while remaining > 0:
            n = min(16, remaining)
            yield {
                "images": rng.standard_normal((n, 32, 32, 3)).astype(np.float32),
                "labels": rng.integers(0, 10, (n,), dtype=np.int32),
            }
            remaining -= n

    metrics = trainer.evaluate(state, eval_iter())
    assert metrics["eval_count"] == 50.0
    assert 0.0 <= metrics["eval_top_1_acc"] <= 1.0


@pytest.mark.slow
def test_eval_tiny_set_smaller_than_mesh(devices):
    """A 3-example eval set on an 8-way data axis must still work."""
    trainer = _trainer()
    state = trainer.init_state()
    rng = np.random.default_rng(1)
    batch = {
        "images": rng.standard_normal((3, 32, 32, 3)).astype(np.float32),
        "labels": rng.integers(0, 10, (3,), dtype=np.int32),
    }
    metrics = trainer.evaluate(state, iter([batch]))
    assert metrics["eval_count"] == 3.0


def test_stored_config_with_the_removed_layout_field_fails_by_name():
    """A config.json written before PR 42 may still carry
    ``fused_optimizer``: ``from_json`` is ``cls(**json.loads(text))``, so
    the unknown key is a ``TypeError`` that names it, and one without the
    key loads as before."""
    import json

    config = _smoke_config()
    assert TrainConfig.from_json(config.to_json()) == config
    stored = {**json.loads(config.to_json()), "fused_optimizer": None}
    with pytest.raises(TypeError, match="fused_optimizer"):
        TrainConfig.from_json(json.dumps(stored))


def test_fused_optimizer_matches_per_leaf():
    """The flat layout (``make_optimizer(fused=True)``) and the per-leaf chain
    compute the same numbers element by element: ``optax.flatten`` changes
    where Adam's moments sit, not what is computed. Three steps with a
    gradient that changes, the global-norm clip engaged on every one, and
    the decay mask deciding leaf by leaf."""
    import jax
    import jax.numpy as jnp
    import optax

    from sav_tpu.train import make_optimizer
    from sav_tpu.train.optimizer import warmup_cosine_schedule, weight_decay_mask

    sched = warmup_cosine_schedule(
        1e-3, steps_per_epoch=10, warmup_epochs=1, num_epochs=10
    )
    params = {
        "encoder": {"kernel": jnp.ones((8, 16)) * 0.3, "bias": jnp.zeros((16,))},
        "pos_embed": {"embedding": jnp.ones((1, 4, 8)) * 0.1},
    }
    assert weight_decay_mask(params) == {
        "encoder": {"kernel": True, "bias": False},
        "pos_embed": {"embedding": False},
    }
    clip = 0.25

    def grads_at(step):
        return jax.tree.map(lambda x: x * 0.05 * (step + 1) + 0.01 * (1 - step), params)

    def run(fused, weight_decay):
        tx = make_optimizer(
            sched, weight_decay=weight_decay, clip_grad_norm=clip, fused=fused
        )
        state, p = tx.init(params), params
        for step in range(3):
            updates, state = tx.update(grads_at(step), state, p)
            p = optax.apply_updates(p, updates)
        return p

    assert all(float(optax.global_norm(grads_at(k))) > clip for k in range(3))
    flat, per_leaf = run(True, 0.05), run(False, 0.05)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-6),
        flat, per_leaf,
    )
    # The mask did decide: without decay only the masked-in kernel differs.
    undecayed = run(False, 0.0)
    assert np.array_equal(per_leaf["encoder"]["bias"], undecayed["encoder"]["bias"])
    assert np.array_equal(
        per_leaf["pos_embed"]["embedding"], undecayed["pos_embed"]["embedding"]
    )
    assert not np.allclose(
        per_leaf["encoder"]["kernel"], undecayed["encoder"]["kernel"], atol=1e-7, rtol=0
    )


def _smoke_batch():
    return {
        "images": np.zeros((16, 32, 32, 3), np.float32),
        "labels": np.arange(16) % 10,
    }


@pytest.mark.slow
def test_logits_dtype_isolated_between_trainers(devices):
    """The softmax dtype is a model *attribute*, so trainers with different
    settings coexist structurally — no process state tracks whose step ran
    last, and nothing a second trainer does can retroactively change what a
    first trainer's lazy traces bake in."""
    from sav_tpu.ops import attention as att

    # Trainer-built models (model_overrides, not an external model) so the
    # config's logits dtype threads through create_model.
    tr_f32 = Trainer(_smoke_config(model_overrides=_small_model_overrides()))
    tr_bf16 = Trainer(
        _smoke_config(
            attention_logits_dtype="bfloat16",
            model_overrides=_small_model_overrides(),
        )
    )
    assert tr_f32.model.logits_dtype is None  # None = inherit compute (f32)
    assert tr_bf16.model.logits_dtype == "bfloat16"
    # Steps of both trainers interleave; the deprecated process fallback
    # never moves because no model path consults or sets it.
    batch = _smoke_batch()
    state = tr_f32.init_state(0)
    state, _ = tr_f32.train_step(state, batch, jax.random.PRNGKey(0))
    state_b = tr_bf16.init_state(0)
    tr_bf16.train_step(state_b, batch, jax.random.PRNGKey(0))
    tr_f32.eval_step(state, batch)
    assert att._DEFAULT_LOGITS_DTYPE == jnp.float32


def test_logits_dtype_ignores_process_global(devices):
    """No jitted model path reads the deprecated process-wide default: a
    block whose attributes say f32 softmax must produce bit-identical
    outputs whatever ``set_default_logits_dtype`` was left at (VERDICT r3
    weak #7 — the hazard class this threading deletes)."""
    from sav_tpu.models.layers.attention import SelfAttentionBlock
    from sav_tpu.ops import attention as att

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), jnp.bfloat16)
    block = SelfAttentionBlock(
        num_heads=4, dtype=jnp.bfloat16, logits_dtype=jnp.float32
    )
    variables = block.init({"params": jax.random.PRNGKey(1)}, x, is_training=False)
    # Un-jitted applies: each run re-executes the dtype resolution, so a
    # regression to reading the global CANNOT hide behind the jit cache
    # (a second jitted call with identical avals would reuse the first
    # trace and compare equal no matter what the global says).
    clean = np.asarray(block.apply(variables, x, is_training=False), np.float32)
    try:
        att.set_default_logits_dtype("bfloat16")  # poison the fallback
        poisoned = np.asarray(
            block.apply(variables, x, is_training=False), np.float32
        )
        # The control: the raw op with logits_dtype=None DOES see the
        # poison — proving the poison is live and the equality below is a
        # property of the block's explicit resolution, not a vacuous pass.
        q = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 4, 8), jnp.bfloat16)
        raw_poisoned = np.asarray(att.xla_attention(q, q, q), np.float32)
        att.set_default_logits_dtype("float32")
        raw_clean = np.asarray(att.xla_attention(q, q, q), np.float32)
        assert not np.array_equal(raw_poisoned, raw_clean)
    finally:
        att.set_default_logits_dtype("float32")
    np.testing.assert_array_equal(poisoned, clean)


def test_logits_dtype_external_model_mismatch_raises(devices):
    """An external model carries its own logits_dtype; a config that says
    otherwise must fail loudly (the old process-global pinning DID apply
    the config to external models — silence would be a regression)."""
    from sav_tpu.models import create_model

    cfg = _smoke_config(
        compute_dtype="bfloat16", attention_logits_dtype="float32"
    )
    model = create_model(
        cfg.model_name, num_classes=10, dtype=jnp.bfloat16,
        **_small_model_overrides(),
    )
    with pytest.raises(ValueError, match="attention_logits_dtype"):
        Trainer(cfg, model=model)
    # Matching attribute: accepted.
    ok = create_model(
        cfg.model_name, num_classes=10, dtype=jnp.bfloat16,
        logits_dtype="float32", **_small_model_overrides(),
    )
    Trainer(cfg, model=ok)


@pytest.mark.slow
def test_logits_dtype_inherits_compute_dtype(devices):
    """attention_logits_dtype=None resolves to the compute dtype — the
    reference's semantics (its logits einsum runs in the model dtype), so
    a bf16-compute trainer softmaxes in bf16 and an f32 one in f32;
    'float32' still forces f32 softmax under bf16 compute. Resolution is
    structural (block attribute), verified by numerics: bf16 vs f32
    softmax differ on the same params/inputs."""
    from sav_tpu.models.layers.attention import SelfAttentionBlock

    tr_forced = Trainer(
        _smoke_config(
            compute_dtype="bfloat16",
            attention_logits_dtype="float32",
            model_overrides=_small_model_overrides(),
        )
    )
    assert tr_forced.model.logits_dtype == "float32"
    tr_inherit = Trainer(
        _smoke_config(
            compute_dtype="bfloat16",
            model_overrides=_small_model_overrides(),
        )
    )
    assert tr_inherit.model.logits_dtype is None

    # Block-level: None inherits the block dtype (bf16 here), and that is
    # a real numerical difference from forcing f32 softmax.
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), jnp.bfloat16)
    inherit = SelfAttentionBlock(num_heads=4, dtype=jnp.bfloat16)
    forced = SelfAttentionBlock(
        num_heads=4, dtype=jnp.bfloat16, logits_dtype=jnp.float32
    )
    variables = inherit.init({"params": jax.random.PRNGKey(1)}, x, is_training=False)
    out_bf16 = np.asarray(
        inherit.apply(variables, x, is_training=False), np.float32
    )
    out_f32 = np.asarray(
        forced.apply(variables, x, is_training=False), np.float32
    )
    assert not np.array_equal(out_bf16, out_f32)


@pytest.mark.slow
def test_warm_start_cross_resolution(tmp_path, devices):
    """--init-from semantics: params transfer, pos_embed resampled to the
    new token count (224->384-style finetune), step/optimizer fresh."""
    overrides = dict(
        num_layers=1, embed_dim=32, num_heads=2, patch_shape=(8, 8)
    )
    cfg32 = _smoke_config(
        checkpoint_dir=str(tmp_path / "pre"), model_overrides=overrides
    )
    pre = Trainer(cfg32)
    state = pre.init_state(0)
    batch = _smoke_batch()
    state, _ = pre.train_step(state, batch, jax.random.PRNGKey(0))
    pre.checkpointer.save(1, state)
    pre.checkpointer.wait()

    cfg48 = _smoke_config(
        image_size=48, model_overrides=overrides, ema_decay=0.999
    )
    fine = Trainer(cfg48)
    warm = fine.warm_start_from(str(tmp_path / "pre"))
    assert int(jax.device_get(warm.step)) == 0  # fresh step + optimizer
    # pos_embed resampled: 32/8 -> 17 tokens, 48/8 -> 37 tokens.
    pe = warm.params["Encoder_0"]["AddAbsPosEmbed_0"]["pos_embed"]
    assert pe.shape[1] == 37
    # Non-positional leaves transfer exactly.
    np.testing.assert_array_equal(
        jax.device_get(warm.params["head"]["kernel"]),
        jax.device_get(state.params["head"]["kernel"]),
    )
    # The parameter EMA is reseeded from the TRANSFERRED weights, not the
    # random init tx.init saw (eval-on-EMA would otherwise start from
    # garbage on short finetunes).
    from sav_tpu.train.optimizer import ema_params

    ema = ema_params(warm.opt_state)
    np.testing.assert_array_equal(
        jax.device_get(ema["head"]["kernel"]),
        jax.device_get(state.params["head"]["kernel"]),
    )
    # ...and as a distinct buffer: the donated train step would otherwise
    # donate the aliased params/EMA buffer twice (runtime crash).
    batch48 = {
        "images": np.zeros((16, 48, 48, 3), np.float32),
        "labels": np.arange(16) % 10,
    }
    warm, metrics = fine.train_step(warm, batch48, jax.random.PRNGKey(1))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_ema_tracks_post_step_params(devices):
    """track_params_ema sits last in the chain, so after one step
    ema == decay·p0 + (1−decay)·p1 exactly; eval runs on the EMA tree."""
    from sav_tpu.train.optimizer import ema_params

    decay = 0.5
    cfg = _smoke_config(
        ema_decay=decay, model_overrides=_small_model_overrides()
    )
    trainer = Trainer(cfg)
    state0 = trainer.init_state(0)
    p0 = jax.device_get(jax.tree.leaves(state0.params)[0])
    ema0 = jax.device_get(jax.tree.leaves(ema_params(state0.opt_state))[0])
    np.testing.assert_array_equal(ema0, p0)  # init: ema == params

    batch = _smoke_batch()
    state1, _ = trainer.train_step(state0, batch, jax.random.PRNGKey(0))
    p1 = jax.device_get(jax.tree.leaves(state1.params)[0])
    ema1 = jax.device_get(jax.tree.leaves(ema_params(state1.opt_state))[0])
    np.testing.assert_allclose(
        ema1, decay * p0 + (1 - decay) * p1, rtol=1e-6, atol=1e-7
    )


@pytest.mark.slow
def test_eval_uses_ema_params(devices):
    """With decay=1.0 the EMA never moves off the init — eval metrics must
    match a fresh model's even after training steps moved the live params."""
    overrides = _small_model_overrides()
    frozen = Trainer(_smoke_config(ema_decay=1.0, model_overrides=overrides))
    live = Trainer(_smoke_config(model_overrides=overrides))
    batch = _smoke_batch()
    rng = jax.random.PRNGKey(0)

    fs = frozen.init_state(0)
    ls = live.init_state(0)
    baseline = float(jax.device_get(frozen.eval_step(fs, batch)["loss_sum"]))
    for i in range(3):
        fs, _ = frozen.train_step(fs, batch, rng)
        ls, _ = live.train_step(ls, batch, rng)
    after_frozen = float(jax.device_get(frozen.eval_step(fs, batch)["loss_sum"]))
    after_live = float(jax.device_get(live.eval_step(ls, batch)["loss_sum"]))
    # decay=1.0: eval-on-EMA pinned to the init weights...
    np.testing.assert_allclose(after_frozen, baseline, rtol=1e-5)
    # ...while the same steps moved the live trainer's eval.
    assert abs(after_live - baseline) > 1e-3
