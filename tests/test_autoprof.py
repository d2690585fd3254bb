"""Anomaly-triggered profiling (ISSUE 7): bounded, budgeted captures.

Unit coverage drives the state machine with injected start/stop fns
(window bounds, budget + cooldown denials, the robust step-time spike
gate, failure containment); the e2e test runs a real fit() on CPU with
an induced goodput stall anomaly and asserts exactly one bounded
jax.profiler capture whose path lands in the run manifest — the ISSUE 7
acceptance criterion.
"""

import json
import os

import numpy as np
import pytest

from sav_tpu.obs.autoprof import TRIGGERS, AutoProfiler
from sav_tpu.train import TrainConfig, Trainer


class SpyProfiler:
    def __init__(self, fail_start=False):
        self.started = []
        self.stopped = 0
        self.fail_start = fail_start

    def start(self, path):
        if self.fail_start:
            raise RuntimeError("trace already active")
        self.started.append(path)

    def stop(self):
        self.stopped += 1


def _prof(tmp_path, spy, **kwargs):
    return AutoProfiler(
        str(tmp_path), start_fn=spy.start, stop_fn=spy.stop, **kwargs
    )


def test_capture_window_is_bounded_and_recorded(tmp_path):
    spy = SpyProfiler()
    prof = _prof(tmp_path, spy, trace_steps=3)
    assert prof.request("stall_anomaly", 10)
    for step in range(10, 20):
        prof.on_step(step)
    assert len(spy.started) == 1 and spy.stopped == 1
    assert len(prof.captures) == 1
    cap = prof.captures[0]
    # Armed at 10, started at the next on_step (10), stopped 3 steps on.
    assert cap["trigger"] == "stall_anomaly"
    assert cap["trigger_step"] == 10
    assert cap["start_step"] == 10 and cap["end_step"] == 13
    assert "proc0_step00000010_stall_anomaly" in cap["path"]
    assert os.path.isdir(cap["path"])
    assert prof.stats()["captures"] == 1.0
    # The per-process sidecar: non-zero processes run with a DISABLED
    # run manifest, so the capture record must exist independently.
    sidecar = os.path.join(str(tmp_path), "autoprof",
                           "proc0_captures.jsonl")
    records = [json.loads(ln) for ln in open(sidecar)]
    assert [r["path"] for r in records] == [cap["path"]]


def test_budget_and_cooldown_deny_further_captures(tmp_path):
    spy = SpyProfiler()
    prof = _prof(
        tmp_path, spy, trace_steps=1, max_captures=2, cooldown_steps=50
    )
    assert prof.request("manual", 1)
    prof.on_step(1)
    prof.on_step(2)  # capture 1 done at step 2
    # Inside the cooldown window: denied.
    assert not prof.request("manual", 10)
    # Past the cooldown: granted; then the budget is spent.
    assert prof.request("manual", 60)
    prof.on_step(60)
    prof.on_step(61)
    assert not prof.request("manual", 200)
    assert prof.stats() == {
        "captures": 2.0, "denied": 2.0, "errors": 0.0,
    }
    # A request while armed/active is denied too (no nesting).
    prof2 = _prof(tmp_path, SpyProfiler(), trace_steps=4)
    assert prof2.request("manual", 1)
    assert not prof2.request("manual", 1)


def test_unknown_trigger_and_bad_knobs_raise(tmp_path):
    spy = SpyProfiler()
    prof = _prof(tmp_path, spy)
    with pytest.raises(ValueError, match="unknown trigger"):
        prof.request("nope", 1)
    assert "stall_anomaly" in TRIGGERS
    with pytest.raises(ValueError):
        AutoProfiler(str(tmp_path), trace_steps=0)
    with pytest.raises(ValueError):
        AutoProfiler(str(tmp_path), max_captures=0)


def test_step_time_spike_gate_is_robust(tmp_path):
    spy = SpyProfiler()
    prof = _prof(
        tmp_path, spy, spike_sigma=4.0, spike_min_history=8,
    )
    # Healthy history: no trigger, gate unarmed until min_history.
    for step in range(1, 9):
        assert prof.note_window(step, 0.1 + 0.001 * (step % 3)) is None
    # A 10x window: the robust gate fires and arms a capture.
    assert prof.note_window(9, 1.0) == "step_time_spike"
    # The spike did NOT enter the history (cannot poison the baseline):
    # after the capture resolves, a second equal spike still fires.
    prof.on_step(10)
    prof.on_step(10 + prof.trace_steps)
    prof2 = _prof(tmp_path, SpyProfiler(), cooldown_steps=0)
    for step in range(1, 9):
        prof2.note_window(step, 0.1)
    assert prof2.note_window(9, 1.0) == "step_time_spike"
    prof2.on_step(9)
    prof2.on_step(9 + prof2.trace_steps)
    assert prof2.note_window(20, 1.0) == "step_time_spike"


def test_start_failure_is_contained_and_rearmable(tmp_path):
    spy = SpyProfiler(fail_start=True)
    prof = _prof(tmp_path, spy, trace_steps=1)
    assert prof.request("manual", 1)
    prof.on_step(1)  # start fails (e.g. static profile window active)
    assert prof.captures == []
    assert prof.stats()["errors"] == 1.0
    assert not prof.active
    # Disarmed, not wedged: a later trigger can try again.
    spy.fail_start = False
    assert prof.request("manual", 5)
    prof.on_step(5)
    prof.on_step(6)
    assert len(prof.captures) == 1


def test_finalize_stops_inflight_capture(tmp_path):
    spy = SpyProfiler()
    prof = _prof(tmp_path, spy, trace_steps=100)
    prof.request("watchdog_soft", 3)
    prof.on_step(3)
    assert prof.active
    prof.finalize(7)  # fit()'s finally: crash mid-window
    assert not prof.active
    assert spy.stopped == 1
    assert prof.captures[0]["end_step"] == 7


# ---------------------------------------------------------------- fit e2e


def test_induced_stall_anomaly_arms_one_bounded_capture(
    tmp_path, devices, monkeypatch
):
    """ISSUE 7 acceptance: an induced goodput stall anomaly arms exactly
    one bounded profiler capture whose path appears in the run manifest.
    The anomaly is induced by flagging one logging window through the
    ledger's real note_window seam — fit()'s wiring (ledger flag →
    autoprof.request → bounded jax.profiler window → manifest stamp)
    runs for real, on the real CPU profiler."""
    from sav_tpu.obs.goodput import GoodputLedger
    from sav_tpu.obs.manifest import RunManifest

    real_note = GoodputLedger.note_window

    def induced(self, num_steps, seconds, step=None):
        flagged = real_note(self, num_steps, seconds, step=step)
        return True if step == 4 else flagged

    monkeypatch.setattr(GoodputLedger, "note_window", induced)
    config = TrainConfig(
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
        autoprof=True,
        autoprof_steps=2,
        autoprof_max=2,
        seed=0,
        model_overrides={"num_layers": 1, "embed_dim": 32, "num_heads": 2},
    )
    trainer = Trainer(config)
    manifest = RunManifest(
        os.path.join(str(tmp_path), "manifest.json"), kind="train"
    )
    manifest.begin()

    def batches(n=10):
        rng = np.random.default_rng(0)
        for _ in range(n):
            yield {
                "images": rng.standard_normal((8, 32, 32, 3)).astype(
                    np.float32
                ),
                "labels": rng.integers(0, 10, (8,), dtype=np.int32),
            }

    trainer.fit(batches(), num_steps=10, manifest=manifest)
    doc = RunManifest.load(manifest.path)
    captures = doc["notes"]["autoprof"]
    assert len(captures) == 1, captures
    cap = captures[0]
    assert cap["trigger"] == "stall_anomaly"
    assert cap["trigger_step"] == 4
    # Bounded: the window spans exactly autoprof_steps steps, starting
    # at the first boundary after the trigger.
    assert cap["end_step"] - cap["start_step"] == 2
    assert os.path.isdir(cap["path"])
    assert str(tmp_path) in cap["path"] and "autoprof" in cap["path"]
    # The real jax.profiler wrote a trace under the capture dir.
    contents = [
        os.path.join(dirpath, f)
        for dirpath, _, files in os.walk(cap["path"]) for f in files
    ]
    assert contents, f"no trace files under {cap['path']}"
    gauges = trainer.last_goodput["gauges"]
    assert gauges["autoprof/captures"] == 1.0
    assert gauges["autoprof/errors"] == 0.0
    # Post-capture trace intelligence (ISSUE 8): the capture was
    # machine-read on the spot — the summary rides the manifest record
    # AND the per-process sidecar, with the measured attribution keyed
    # exactly like the cost model's predicted one.
    from sav_tpu.obs.costs import COMP_ATTN_QKAV

    sidecar = os.path.join(str(tmp_path), "autoprof",
                           "proc0_captures.jsonl")
    records = [json.loads(ln) for ln in open(sidecar)]
    for record in (cap, records[-1]):
        summary = record["summary"]
        assert summary["per_step_ms"] > 0
        assert summary["device_selector"] == "cpu-hlo-op"
        assert summary["indexed_frac"] > 0.5  # the HLO op index resolved
        measured = summary["components_frac"]
        doc2 = RunManifest.load(manifest.path)
        predicted = doc2["notes"]["cost_model"]["attribution"]
        assert set(predicted).issubset(set(measured))
        assert summary["attention_core_frac"] == pytest.approx(
            measured[COMP_ATTN_QKAV], abs=1e-3
        )
        assert "disagrees" in summary
    # The capture dir carries the offline tools' inputs: the op index
    # and the full summary (tools/trace_report.py reads both).
    assert os.path.exists(os.path.join(cap["path"], "op_index.json"))
    with open(os.path.join(cap["path"], "trace_summary.json")) as f:
        full = json.load(f)
    assert full["vs_predicted"]["rows"]
    assert full["steps"] == 2  # the bounded window's own step count
    # ISSUE 8 acceptance: the capture round-trips through the offline
    # CLI (auto-discovering trace, op index, and the manifest's
    # predicted attribution) into a per-layer-group measured table
    # whose groups are the same keys obs/costs.py predicts.
    import importlib.util
    import sys as _sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(root, "tools", "trace_report.py")
    )
    trace_report = importlib.util.module_from_spec(spec)
    _sys.modules[spec.name] = trace_report
    spec.loader.exec_module(trace_report)
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = trace_report.main([str(tmp_path), "--json"])
    assert rc == 0
    cli = json.loads(buf.getvalue())
    predicted_groups = set(doc2["notes"]["cost_model"]["groups"])
    measured_groups = set(cli["groups_frac"])
    # Every measured group is a predicted group, one of the train step's
    # own named scopes (no parameters, so the cost model predicts none),
    # or the honest 'other' bucket for bare top-level primitives.
    step_scopes = {"preprocess", "loss", "optimizer", "metrics"}
    assert measured_groups - {"other"} - step_scopes <= predicted_groups
    assert measured_groups & step_scopes, cli["groups_frac"]
    assert measured_groups & predicted_groups, cli["groups_frac"]
    assert cli["vs_predicted"]["rows"]


def test_analysis_failure_is_contained(tmp_path):
    """A broken op_index_fn (or unparseable trace) counts as an error
    gauge; the capture record still lands without its summary."""

    def boom():
        raise RuntimeError("no HLO for you")

    spy = SpyProfiler()
    prof = AutoProfiler(
        str(tmp_path), start_fn=spy.start, stop_fn=spy.stop,
        trace_steps=1, op_index_fn=boom,
    )
    # Plant a trace file so analysis actually runs into the bad index fn.
    assert prof.request("manual", 1)
    prof.on_step(1)
    os.makedirs(os.path.join(prof._active["path"]), exist_ok=True)
    import gzip

    with gzip.open(
        os.path.join(prof._active["path"], "x.trace.json.gz"), "wt"
    ) as f:
        f.write('{"traceEvents": []}')
    prof.on_step(2)
    assert len(prof.captures) == 1
    assert "summary" not in prof.captures[0]
    assert prof.stats()["errors"] == 1.0
