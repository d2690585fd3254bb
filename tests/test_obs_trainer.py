"""End-to-end telemetry through Trainer.fit() on the virtual CPU mesh:
in-jit diagnostics ride the step metrics, the span trace is
Perfetto-loadable JSON, the goodput ledger's buckets sum to wall time
within 5%, and an armed watchdog does not false-fire on a healthy run
(ISSUE 1 acceptance criteria)."""

import json
import os
import time

import jax.numpy as jnp
import pytest

from sav_tpu.data import fake_data_iterator
from sav_tpu.train import TrainConfig, Trainer


def _obs_trainer(tmp_path, **config_overrides):
    from sav_tpu.models import create_model

    base = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=8,
        num_train_images=8 * 4,
        num_epochs=1,
        warmup_epochs=1,
        lr_scaling_divisor=8,
        transpose_images=False,
        log_every_steps=2,
        log_dir=str(tmp_path),
        diagnostics=True,
        trace_spans=True,
        seed=0,
    )
    base.update(config_overrides)
    config = TrainConfig(**base)
    model = create_model(
        config.model_name,
        num_classes=config.num_classes,
        dtype=jnp.float32,
        num_layers=2,
        embed_dim=64,
        num_heads=4,
    )
    return Trainer(config, model=model)


def test_fit_emits_diagnostics_spans_and_goodput(tmp_path, devices):
    # async_feed=False pins the *serial* loop's telemetry contract
    # (batch_wait/shard_batch spans, h2d bucket on the training thread);
    # feeder-mode telemetry is covered in tests/test_feeder.py.
    trainer = _obs_trainer(tmp_path, watchdog_secs=300.0, async_feed=False)
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    t0 = time.perf_counter()
    state, history = trainer.fit(data, num_steps=4, log_fn=None)
    wall = time.perf_counter() - t0

    # --- in-jit diagnostics ride the logged step metrics ---
    train_records = [m for m in history if "loss" in m]
    assert train_records, "no training metrics logged"
    m = train_records[-1]
    for key in (
        "grad_norm", "param_norm", "update_norm", "update_to_param_ratio",
    ):
        assert key in m and m[key] >= 0.0, key
    assert m["nonfinite_grads"] == 0.0
    assert m["nonfinite_params"] == 0.0
    group_keys = [k for k in m if k.startswith("grad_norm/")]
    assert group_keys, "per-layer-group grad norms missing"
    assert "retraces" in m

    # --- span trace: Perfetto-loadable, covers the loop's phases ---
    span_path = os.path.join(str(tmp_path), "spans.trace.json")
    assert os.path.exists(span_path)
    with open(span_path) as f:
        events = json.load(f)["traceEvents"]
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {
        "sav:fit/batch_wait", "sav:fit/shard_batch", "sav:fit/dispatch",
        "sav:fit/log_sync",
    } <= names

    # --- goodput ledger: buckets sum to wall time within 5% ---
    goodput_path = os.path.join(str(tmp_path), "goodput.json")
    assert os.path.exists(goodput_path)
    with open(goodput_path) as f:
        summary = json.load(f)
    bucket_sum = sum(summary["buckets_s"].values())
    assert bucket_sum == pytest.approx(summary["wall_s"], rel=0.05)
    # The ledger's wall clock must agree with the caller's stopwatch.
    assert summary["wall_s"] <= wall * 1.05
    assert summary["steps"] == 4
    assert summary["buckets_s"]["compile"] > 0.0  # first jit dispatch
    # Serial loop books placement separately from fetch (ISSUE 2): the
    # shard_batch device_put lands in h2d, not input_wait.
    assert summary["buckets_s"]["h2d"] > 0.0
    assert summary["num_anomalies"] == 0

    # --- goodput record also lands in the returned history ---
    goodput_records = [m for m in history if "goodput/wall_s" in m]
    assert goodput_records
    assert trainer.last_goodput is not None

    # --- an armed watchdog did not false-fire on this healthy run ---
    # (fit() would have os._exit'd the test process if it had.)
    assert int(history[-1]["step"]) == 4


def test_fit_without_obs_flags_keeps_legacy_metrics(tmp_path, devices):
    trainer = _obs_trainer(
        tmp_path, diagnostics=False, trace_spans=False, log_dir=None,
        checkpoint_dir=None,
    )
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    _, history = trainer.fit(data, num_steps=2, log_fn=None)
    train_records = [m for m in history if "loss" in m]
    assert train_records
    assert "param_norm" not in train_records[-1]
    assert not os.path.exists(os.path.join(str(tmp_path), "spans.trace.json"))
    # The goodput ledger itself is always on (zero-cost); only files are
    # gated on a sink dir.
    assert trainer.last_goodput is not None
    assert not os.path.exists(os.path.join(str(tmp_path), "goodput.json"))
