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


# ------------------------------------------------- the observer seam (ISSUE 28)
#
# fit() calls one list of observers (sav_tpu/obs/fit_observers.py) and knows
# no listener by name. A test hands the loop a listener of its own by
# wrapping the factory fit() imports at call time.


def _with_observers(monkeypatch, *, extra=(), spy_exit=None):
    """Have fit() run with the real observers plus ``extra``; ``spy_exit``
    (a list) receives ``(observer class name, exc, feeder)`` per exit."""
    from sav_tpu.obs import fit_observers

    real_build = fit_observers.build_observers

    def build(cfg, **kwargs):
        real = real_build(cfg, **kwargs)
        observers = [*real.observers, *extra]
        if spy_exit is not None:
            for o in observers:
                def exit(exc, state, feeder, _o=o, _exit=o.exit):
                    spy_exit.append((type(_o).__name__, exc, feeder))
                    _exit(exc, state, feeder)
                o.exit = exit
        return fit_observers.FitObservers(observers, real.recorder)

    monkeypatch.setattr(fit_observers, "build_observers", build)


def _recording_observer():
    from sav_tpu.obs.fit_observers import EVENTS, FitObserver

    seen = []

    def record(event):
        def method(self, *args):
            # Step numbers where the event has one; the live objects
            # (state, batch, executable, metrics) are not kept.
            seen.append((event, *[a for a in args if isinstance(a, int)]))
        return method

    Recording = type(
        "Recording", (FitObserver,), {event: record(event) for event in EVENTS}
    )
    return Recording(), seen


@pytest.mark.parametrize("async_feed", [True, False], ids=["fed", "serial"])
def test_observer_sees_the_loops_moments_in_order(
    tmp_path, devices, monkeypatch, async_feed
):
    observer, seen = _recording_observer()
    _with_observers(monkeypatch, extra=[observer])
    trainer = _obs_trainer(tmp_path, async_feed=async_feed)
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    trainer.fit(data, num_steps=5)
    # The fed path observes host batches on the feeder's thread, ahead of
    # the loop by the queue's depth: checked apart from the loop's order.
    batches = [e for e in seen if e[0] == "host_batch"]
    assert len(batches) >= 5 if async_feed else len(batches) == 5
    batch = [] if async_feed else [("host_batch",)]
    loop = [e for e in seen if e[0] != "host_batch" or not async_feed]
    assert loop == [
        ("before_step", 0), *batch, ("after_step", 1), ("first_step",),
        ("before_step", 1), *batch, ("after_step", 2),
        ("log", 2, 2), ("logged", 2),
        ("before_step", 2), *batch, ("after_step", 3),
        ("before_step", 3), *batch, ("after_step", 4),
        ("log", 4, 2), ("logged", 4),
        ("before_step", 4), *batch, ("after_step", 5),
        ("log", 5, 1), ("logged", 5),  # the last step logs whatever is left
        ("loop_done",), ("exit",),
    ]


class _FailsAt:
    """The jitted step, raising at its ``n``-th call."""

    def __init__(self, step_fn, n):
        self._step_fn, self._n, self._calls = step_fn, n, 0

    def __call__(self, *args):
        self._calls += 1
        if self._calls == self._n:
            raise RuntimeError("the step failed")
        return self._step_fn(*args)

    def __getattr__(self, name):
        return getattr(self._step_fn, name)


def test_step_exception_reaches_every_exit_in_the_documented_order(
    tmp_path, devices, monkeypatch
):
    from sav_tpu.obs.manifest import RunManifest

    exits = []
    _with_observers(monkeypatch, spy_exit=exits)
    trainer = _obs_trainer(
        tmp_path, record=True, autoprof=True, sanitize=True,
        watchdog_secs=300.0, checkpoint_dir=str(tmp_path / "ckpt"),
    )
    trainer._train_step = _FailsAt(trainer._train_step, 3)
    manifest = RunManifest(os.path.join(str(tmp_path), "manifest.json"), kind="train")
    manifest.begin()
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    with pytest.raises(RuntimeError, match="the step failed"):
        trainer.fit(data, num_steps=6, manifest=manifest)
    # sav_tpu/obs/fit_observers.py's module docstring, class by class.
    assert [name for name, _, _ in exits] == [
        "_Recorder", "_MemDump", "_Feeder", "_Watchdog", "_CheckpointDrain",
        "_Autoprof", "_Fleet", "_Sanitizer", "_Cost", "_Memory", "_Manifest",
    ]
    assert all(
        isinstance(exc, RuntimeError) and "the step failed" in str(exc)
        for _, exc, _ in exits
    )
    feeder = exits[0][2]
    assert feeder is not None and not feeder._thread.is_alive()
    # The crashed run still reported: the recorder's crash bundle, the
    # heartbeat's final line, the manifest's notes and metrics.
    doc = RunManifest.load(manifest.path)
    assert [i["trigger"] for i in doc["notes"]["incidents"]] == ["exception"]
    assert {"backend", "layout", "cost_model", "hbm", "fleet"} <= set(doc["notes"])
    assert doc["metrics"]["goodput/recorder/incidents"] == 1.0
    with open(os.path.join(str(tmp_path), "fleet", "proc_0.jsonl")) as f:
        final = json.loads(f.read().splitlines()[-1])
    assert (final["kind"], final["outcome"]) == ("final", "error")


def test_fit_with_no_switch_on_imports_no_observer_it_does_not_need(tmp_path):
    import subprocess
    import sys

    script = (
        "import sys\n"
        "import jax.numpy as jnp\n"
        "from sav_tpu.data import fake_data_iterator\n"
        "from sav_tpu.models import create_model\n"
        "from sav_tpu.train import TrainConfig, Trainer\n"
        "config = TrainConfig(model_name='vit_ti_patch16', num_classes=10, image_size=32,\n"
        "    compute_dtype='float32', global_batch_size=8, num_train_images=32, num_epochs=1,\n"
        "    warmup_epochs=0, transpose_images=False, log_every_steps=2, seed=0,\n"
        f"    log_dir={str(tmp_path)!r})\n"
        "model = create_model(config.model_name, num_classes=10, dtype=jnp.float32,\n"
        "    num_layers=1, embed_dim=32, num_heads=2)\n"
        "_, history = Trainer(config, model=model).fit(\n"
        "    fake_data_iterator(batch_size=8, image_size=32, num_classes=10), num_steps=2)\n"
        "assert 'mfu' in history[0] and 'goodput/hbm/peak_bytes' in history[-1]\n"
        "loaded = [m for m in ('sav_tpu.obs.recorder', 'sav_tpu.obs.autoprof',\n"
        "    'sav_tpu.obs.traceview', 'sav_tpu.obs.watchdog', 'sav_tpu.analysis.sanitize')\n"
        "    if m in sys.modules]\n"
        "assert not loaded, loaded\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=root, capture_output=True,
        text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    # What is always on still ran: the heartbeat stream has its lines.
    assert os.path.exists(os.path.join(str(tmp_path), "fleet", "proc_0.jsonl"))


def test_key_added_at_the_log_boundary_reaches_history_and_log_fn(
    tmp_path, devices, monkeypatch
):
    from sav_tpu.obs.fit_observers import FitObserver

    class AddsKey(FitObserver):
        def log(self, step, metrics, steps_since, wall_s):
            metrics["steps_in_window"] = float(steps_since)

    _with_observers(monkeypatch, extra=[AddsKey()])
    trainer = _obs_trainer(tmp_path, diagnostics=False)
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    logged = []
    _, history = trainer.fit(data, num_steps=3, log_fn=logged.append)
    lines = [m for m in history if "loss" in m]
    assert [(m["step"], m["steps_in_window"]) for m in lines] == [(2, 2.0), (3, 1.0)]
    assert [m for m in logged if "loss" in m] == lines
    # After what is always on: mfu is the cost observer's key.
    keys = list(lines[0])
    assert keys.index("mfu") < keys.index("steps_in_window")


def test_ahead_of_time_side_runs_through_the_seam(tmp_path, devices, monkeypatch):
    """``use_aot`` is the path fit() takes on the chip (a real peak); tier-1
    otherwise runs the jit side only. An overridden peak takes it here: the
    ``compiled`` event upgrades the cost's total to XLA's count, and the
    profiler's observer reads its op index off the executable it was
    handed, with no second lowering of the step."""
    from sav_tpu.obs.goodput import GoodputLedger
    from sav_tpu.obs.manifest import RunManifest

    real_note = GoodputLedger.note_window

    def induced(self, num_steps, seconds, step=None):
        return real_note(self, num_steps, seconds, step=step) or step == 4

    monkeypatch.setattr(GoodputLedger, "note_window", induced)
    trainer = _obs_trainer(
        tmp_path, peak_flops=1e12, autoprof=True, autoprof_steps=2,
        autoprof_max=1, diagnostics=False,
    )
    lowered = []
    real_lower = trainer._train_step.lower
    monkeypatch.setattr(
        trainer, "_train_step",
        type("Step", (), {
            "lower": staticmethod(lambda *a: lowered.append(1) or real_lower(*a)),
            "__call__": lambda self, *a: pytest.fail("the jit side ran"),
        })(),
    )
    manifest = RunManifest(os.path.join(str(tmp_path), "manifest.json"), kind="train")
    manifest.begin()
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    _, history = trainer.fit(data, num_steps=10, manifest=manifest)
    assert lowered == [1]
    doc = RunManifest.load(manifest.path)
    cost = doc["notes"]["cost_model"]
    assert cost["source"] == "xla-cost-analysis"
    assert cost["peak_flops_source"] == "override"
    assert doc["metrics"]["goodput/flops/step_per_device"] == cost["flops_per_device"]
    assert all(m["mfu"] > 0 for m in history if "loss" in m)
    assert trainer.last_goodput["buckets_s"]["compile"] > 0.0  # the AOT span
    (capture,) = doc["notes"]["autoprof"]
    assert capture["trigger"] == "stall_anomaly"
    assert capture["summary"]["indexed_frac"] > 0.5  # the executable's text
    assert os.path.exists(os.path.join(capture["path"], "op_index.json"))
