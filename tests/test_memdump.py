"""Memory forensics (ISSUE 8): OOM incident bundles + the HBM watermark.

Unit coverage drives the watermark and the classification/budget
machinery directly; the e2e test plants an allocation failure inside a
real fit() on CPU and asserts the ISSUE 8 acceptance chain: manifest
outcome ``oom``, the peak-HBM manifest field set on the crash path, and
a memdump incident bundle with a non-empty live-buffer ranking that
``tools/run_report.py`` renders.
"""

import importlib.util
import io
import json
import os
import sys

import numpy as np
import pytest

from sav_tpu.obs.memdump import (
    HbmWatermark,
    dump_memory_incident,
    live_buffer_ranking,
)
from sav_tpu.obs.manifest import RunManifest, classify_exception
from sav_tpu.train import TrainConfig, Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def _tiny_config(tmp_path, **overrides):
    kwargs = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=8,
        num_train_images=8 * 32,
        num_epochs=1,
        warmup_epochs=0,
        base_lr=1e-3,
        transpose_images=False,
        log_every_steps=2,
        log_dir=str(tmp_path),
        seed=0,
        model_overrides={"num_layers": 1, "embed_dim": 32, "num_heads": 2},
    )
    kwargs.update(overrides)
    return TrainConfig(**kwargs)


def _batches(n=100, fail_at=None):
    rng = np.random.default_rng(0)
    for i in range(n):
        if fail_at is not None and i == fail_at:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 9876543210 bytes"
            )
        yield {
            "images": rng.standard_normal((8, 32, 32, 3)).astype(
                np.float32
            ),
            "labels": rng.integers(0, 10, (8,), dtype=np.int32),
        }


# ---------------------------------------------------------------- watermark


def test_watermark_tracks_peak_from_device_stats():
    wm = HbmWatermark()
    wm.observe({"hbm_bytes_in_use": 100.0, "hbm_peak_bytes": 120.0})
    wm.observe({"hbm_bytes_in_use": 80.0, "hbm_peak_bytes": 90.0,
                "hbm_bytes_limit": 1000.0})
    assert wm.peak_bytes == 120.0  # peak never regresses
    assert wm.in_use_bytes == 80.0
    assert wm.limit_bytes == 1000.0
    assert wm.source == "device-stats"
    assert wm.samples == 2


def test_watermark_never_folds_summed_in_use_into_per_device_peak():
    """hbm_stats' in_use is a SUM over devices, peak a per-device MAX:
    on a 4-device host the sum must not masquerade as the OOM-relevant
    per-device peak."""
    wm = HbmWatermark()
    wm.observe({"hbm_bytes_in_use": 40e9, "hbm_peak_bytes": 15.9e9})
    assert wm.peak_bytes == 15.9e9
    assert wm.in_use_bytes == 40e9
    # Only a backend with NO peak counter degrades to the sum.
    wm2 = HbmWatermark()
    wm2.observe({"hbm_bytes_in_use": 500.0})
    assert wm2.peak_bytes == 500.0


def test_watermark_empty_stats_are_noops():
    wm = HbmWatermark()
    wm.observe({})
    assert wm.samples == 0 and wm.source is None


def test_watermark_finalize_backfills_live_arrays_on_cpu(devices):
    """CPU reports no memory_stats; finalize() must still produce a
    nonzero watermark (labeled live-arrays) so the manifest field exists
    in tier-1."""
    import jax

    anchor = jax.device_put(np.ones((64, 64), np.float32))
    wm = HbmWatermark()
    record = wm.finalize()
    assert record["peak_bytes"] >= anchor.nbytes
    assert record["source"] == "live-arrays"
    del anchor


# ------------------------------------------------------------ live ranking


def test_live_buffer_ranking_classifies_state_by_identity(devices):
    from sav_tpu.obs.costs import param_group_bytes

    import jax

    config = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        compute_dtype="float32", global_batch_size=8,
        transpose_images=False, seed=0,
        model_overrides={"num_layers": 1, "embed_dim": 32, "num_heads": 2},
    )
    trainer = Trainer(config)
    state = trainer.init_state(0)
    stray = jax.device_put(np.ones((7, 11), np.float32))  # unattributed
    ranking = live_buffer_ranking(state, limit=5)
    assert ranking is not None
    classes = ranking["class_bytes"]
    # Live params-class bytes match the cost model's shape-derived
    # estimate exactly (no donation leak in a fresh state).
    estimate = param_group_bytes(state.params)
    assert classes["params"] == pytest.approx(estimate["_total"])
    assert classes["opt_state"] > 0
    assert classes["unattributed"] >= stray.nbytes
    assert ranking["num_buffers"] >= 5
    assert len(ranking["buffers"]) == 5
    assert ranking["truncated"] >= 0
    # rows are size-ranked and carry param groups
    sizes = [r["bytes"] for r in ranking["buffers"]]
    assert sizes == sorted(sizes, reverse=True)
    # ... among all the rows, not the top five: the ranking is of the
    # process's live buffers, and a test that ran earlier on this worker may
    # have left larger ones than this tiny model's parameters.
    every_row = live_buffer_ranking(state, limit=10**6)["buffers"]
    assert any(r["group"] for r in every_row if r["class"] == "params")
    del stray


def test_dump_budget_and_containment(tmp_path, devices):
    for i in range(2):
        assert dump_memory_incident(
            str(tmp_path), step=i, error="x", max_dumps=2
        ) is not None
    # budget spent -> refused, not raised
    assert dump_memory_incident(
        str(tmp_path), step=9, error="x", max_dumps=2
    ) is None
    assert len(os.listdir(tmp_path / "incidents")) == 2


# ----------------------------------------------------------------- fit e2e


def test_planted_oom_produces_forensics_bundle(tmp_path, devices, capsys):
    """ISSUE 8 acceptance: a planted allocation failure ends with
    manifest outcome `oom`, the peak-HBM field set, and a memdump bundle
    (non-empty live-buffer ranking) that run_report.py renders."""
    config = _tiny_config(tmp_path)
    trainer = Trainer(config)
    manifest = RunManifest(
        os.path.join(str(tmp_path), "manifest.json"), kind="train"
    )
    manifest.begin()
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        try:
            trainer.fit(
                _batches(fail_at=5), num_steps=20, manifest=manifest
            )
        except BaseException as e:  # train.py's shell, inlined
            manifest.finalize(
                classify_exception(e), error=repr(e), exit_code=1
            )
            raise
    doc = RunManifest.load(manifest.path)
    assert doc["outcome"] == "oom"
    # The watermark is a first-class manifest field, set on the crash
    # path (the satellite contract: no goodput.json needed).
    assert doc["metrics"]["hbm_peak_bytes"] > 0
    assert doc["notes"]["hbm"]["source"] in ("device-stats", "live-arrays")
    md = doc["notes"]["memdump"]
    assert md["trigger"] == "oom"
    bundle = md["path"]
    with open(os.path.join(bundle, "memdump.json")) as f:
        dump = json.load(f)
    assert dump["trigger"] == "oom"
    assert "RESOURCE_EXHAUSTED" in dump["error"]
    live = dump["live"]
    assert live["buffers"], "live-buffer ranking must be non-empty"
    assert live["class_bytes"]["params"] > 0
    assert dump["param_group_bytes"]["_total"] > 0
    assert dump["watermark"]["peak_bytes"] > 0
    # run_report renders both the manifest flag and the bundle.
    run_report = _load_tool("run_report")
    assert run_report.main([str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "MEMDUMP" in text
    assert "memdump_" in text
    assert "by class:" in text
    assert "HBM watermark" in text


def test_non_oom_crash_does_not_dump(tmp_path, devices):
    config = _tiny_config(tmp_path)
    trainer = Trainer(config)

    def batches():
        yield from _batches(n=3)
        raise ValueError("plain crash, not an allocator failure")

    with pytest.raises(ValueError):
        trainer.fit(batches(), num_steps=20)
    root = os.path.join(str(tmp_path), "incidents")
    assert not os.path.isdir(root) or not [
        d for d in os.listdir(root) if d.startswith("memdump_")
    ]


def test_memdump_knob_off_still_stamps_watermark(tmp_path, devices):
    config = _tiny_config(tmp_path, memdump=False)
    trainer = Trainer(config)
    manifest = RunManifest(
        os.path.join(str(tmp_path), "manifest.json"), kind="train"
    )
    manifest.begin()
    with pytest.raises(RuntimeError):
        trainer.fit(_batches(fail_at=3), num_steps=20, manifest=manifest)
    doc = RunManifest.load(manifest.path)
    # no forensics bundle...
    assert "memdump" not in doc["notes"]
    # ...but the watermark field exists on every exit path regardless.
    assert doc["metrics"]["hbm_peak_bytes"] > 0


def test_healthy_run_stamps_watermark_and_no_bundle(tmp_path, devices):
    config = _tiny_config(tmp_path)
    trainer = Trainer(config)
    manifest = RunManifest(
        os.path.join(str(tmp_path), "manifest.json"), kind="train"
    )
    manifest.begin()
    trainer.fit(_batches(n=4), num_steps=4, manifest=manifest)
    doc = RunManifest.load(manifest.path)
    assert doc["metrics"]["hbm_peak_bytes"] > 0
    assert "memdump" not in doc["notes"]
    gauges = trainer.last_goodput["gauges"]
    assert gauges["hbm/peak_bytes"] > 0
