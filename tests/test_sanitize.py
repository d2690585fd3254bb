"""Runtime sanitizer (ISSUE 3): StepSanitizer unit + Trainer integration.

Unit tier: the transfer guard rejects implicit host→device transfers
while armed and unwinds cleanly on close. Integration tier:
``TrainConfig.sanitize=True`` is silent on a healthy run (the acceptance
criterion for ``train.py --sanitize``) and composes with the feeder, the
serial fallback, and diagnostics. A batch whose shape drifts mid-fit
fails at the offending step with the flag or without it: the loop calls
one executable, which refuses what it was not compiled for.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sav_tpu.analysis.sanitize import StepSanitizer


# -------------------------------------------------------------- unit tier


def test_transfer_guard_blocks_implicit_h2d_until_close():
    f = jax.jit(lambda x: x + 1)
    placed = jnp.ones(4)
    san = StepSanitizer()
    f(placed)
    san.arm()
    f(placed)  # device-resident arg: fine
    # Explicit placement stays legal — the feeder/serial-fallback contract.
    f(jax.device_put(np.ones(4)))
    with pytest.raises(Exception, match="[Dd]isallow"):
        f(np.ones(4))  # implicit host->device upload
    san.close()
    f(np.ones(4))  # guard unwound


def test_sanitizer_is_idempotent_and_safe_unarmed():
    san = StepSanitizer()
    san.close()  # before arm: no-op
    san.arm()
    san.arm()  # double-arm: no double guard entry
    assert san.armed
    san.close()
    san.close()
    assert not san.armed
    jnp.asarray(np.ones(2)) + 1  # guard unwound: implicit uploads are legal


# ------------------------------------------------------- integration tier


def _trainer(**config_overrides):
    from sav_tpu.models import create_model
    from sav_tpu.train import TrainConfig, Trainer

    base = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=16,
        num_train_images=16 * 4,
        num_epochs=2,
        warmup_epochs=1,
        lr_scaling_divisor=16,
        transpose_images=False,
        log_every_steps=2,
        sanitize=True,
        seed=0,
    )
    base.update(config_overrides)
    config = TrainConfig(**base)
    model = create_model(
        config.model_name, num_classes=config.num_classes,
        dtype=jnp.float32, num_layers=2, embed_dim=64, num_heads=4,
    )
    return Trainer(config, model=model)


def _batches(n, batch_size=16, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "images": rng.standard_normal(
                (batch_size, 32, 32, 3)
            ).astype(np.float32),
            "labels": rng.integers(0, 10, (batch_size,), np.int32),
        }
        for _ in range(n)
    ]


def test_fit_with_sanitize_completes_silently(devices):
    """The acceptance path behind `train.py --sanitize`: a healthy run
    (async feeder on) finishes with guards armed and nothing fired."""
    trainer = _trainer()
    state, history = trainer.fit(iter(_batches(4)), num_steps=4)
    assert int(jax.device_get(state.step)) == 4
    assert trainer.last_goodput["gauges"]["feeder/batches"] == 4.0


def test_fit_with_sanitize_serial_fallback(devices):
    """async_feed=False places batches inline but EXPLICITLY — the
    transfer guard must accept the sanctioned serial path too."""
    trainer = _trainer(async_feed=False)
    state, _ = trainer.fit(iter(_batches(3)), num_steps=3)
    assert int(jax.device_get(state.step)) == 3


def test_fit_with_sanitize_and_diagnostics_coexist(devices):
    """A quiet run under both switches: the guard fires on nothing the
    diagnostics read, and no line counts a compile."""
    trainer = _trainer(diagnostics=True)
    state, history = trainer.fit(iter(_batches(4)), num_steps=4)
    assert int(jax.device_get(state.step)) == 4
    logged = [h for h in history if "retraces" in h]
    assert logged and all(h["retraces"] == 0.0 for h in logged)


@pytest.mark.parametrize("sanitize", [False, True])
def test_fit_drifted_batch_fails_at_the_offending_step(
    devices, tmp_path, monkeypatch, sanitize
):
    """A batch whose shape drifts mid-run is refused by the executable at
    that step, with ``sanitize`` or without: two steps ran, the third
    raised the executable's own ``TypeError``, every observer's exit ran in
    the documented order and the manifest carries the run's metrics."""
    from test_obs_trainer import _with_observers

    from sav_tpu.obs.manifest import RunManifest, classify_exception

    exits = []
    _with_observers(monkeypatch, spy_exit=exits)
    batches = _batches(2) + _batches(1, batch_size=8)
    trainer = _trainer(sanitize=sanitize, log_dir=str(tmp_path))
    manifest = RunManifest(str(tmp_path / "manifest.json"), kind="train")
    manifest.begin()
    logged = []
    with pytest.raises(TypeError, match="compiled with.*16.*called with.*8") as info:
        trainer.fit(
            iter(batches), num_steps=3, manifest=manifest, log_fn=logged.append
        )
    assert [m["step"] for m in logged] == [2]  # two steps ran and logged
    assert [name for name, _, _ in exits] == [
        "_MemDump", "_Feeder", "_Fleet", *(["_Sanitizer"] if sanitize else []),
        "_Cost", "_Memory", "_Manifest",
    ]
    assert all(isinstance(exc, TypeError) for _, exc, _ in exits)
    manifest.finalize(classify_exception(info.value), error=repr(info.value))
    doc = RunManifest.load(manifest.path)
    assert doc["outcome"] == "error"
    assert doc["metrics"]["goodput/step_s"] > 0.0
