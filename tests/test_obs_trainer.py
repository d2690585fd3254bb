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
    assert summary["buckets_s"]["compile"] > 0.0  # the fit/compile span
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
        ("before_step", 0), *batch, ("compiled",), ("after_step", 1),
        ("first_step",),
        ("before_step", 1), *batch, ("after_step", 2),
        ("log", 2, 2), ("logged", 2),
        ("before_step", 2), *batch, ("after_step", 3),
        ("before_step", 3), *batch, ("after_step", 4),
        ("log", 4, 2), ("logged", 4),
        ("before_step", 4), *batch, ("after_step", 5),
        ("log", 5, 1), ("logged", 5),  # the last step logs whatever is left
        ("loop_done",), ("exit",),
    ]


class _Executable:
    """The step's executable, counting its calls and raising at the
    ``fail_at``-th."""

    def __init__(self, executable, fail_at=None):
        self._executable, self._fail_at, self.calls = executable, fail_at, 0

    def __call__(self, *args):
        self.calls += 1
        if self.calls == self._fail_at:
            raise RuntimeError("the step failed")
        return self._executable(*args)

    def __getattr__(self, name):
        return getattr(self._executable, name)


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
    compile_step = trainer.compile_train_step
    monkeypatch.setattr(
        trainer, "compile_train_step", lambda *a: _Executable(compile_step(*a), 3)
    )
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


def _induce_stall_at(monkeypatch, at):
    """Have the ledger flag the window that ends at step ``at``."""
    from sav_tpu.obs.goodput import GoodputLedger

    real_note = GoodputLedger.note_window

    def induced(self, num_steps, seconds, step=None):
        return real_note(self, num_steps, seconds, step=step) or step == at

    monkeypatch.setattr(GoodputLedger, "note_window", induced)


def test_ahead_of_time_side_runs_through_the_seam(tmp_path, devices, monkeypatch):
    """The one path ``fit`` has, on the CPU as on the chip: the ``compiled``
    event upgrades the cost's total to XLA's count, and the profiler's
    observer reads its op index off the executable it was handed."""
    from sav_tpu.obs.manifest import RunManifest

    _induce_stall_at(monkeypatch, 4)
    trainer = _obs_trainer(
        tmp_path, peak_flops=1e12, autoprof=True, autoprof_steps=2,
        autoprof_max=1, diagnostics=False,
    )
    manifest = RunManifest(os.path.join(str(tmp_path), "manifest.json"), kind="train")
    manifest.begin()
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    _, history = trainer.fit(data, num_steps=10, manifest=manifest)
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


# ---------------------------------------- one compiled step a fit (ISSUE 42)


def test_plain_fit_compiles_once_and_dispatches_the_executable(
    tmp_path, devices, monkeypatch
):
    """No peak override, no switch: the CPU takes the path the chip takes.
    One ``fit/compile`` span, booked as the ledger's compile bucket and kept
    for the timeline; one ``compiled`` event; every step a ``fit/dispatch``
    of that executable; the jitted function itself is never called."""
    from sav_tpu.obs import spans

    observer, seen = _recording_observer()
    _with_observers(monkeypatch, extra=[observer])
    trainer = _obs_trainer(tmp_path, diagnostics=False)
    handed, compile_step = [], trainer.compile_train_step
    monkeypatch.setattr(
        trainer, "compile_train_step",
        lambda *a: handed.append(_Executable(compile_step(*a))) or handed[-1],
    )
    jitted = trainer._train_step
    monkeypatch.setattr(
        trainer, "_train_step",
        type("Step", (), {
            "lower": staticmethod(jitted.lower),
            "__call__": lambda self, *a: pytest.fail("the jitted step was called"),
        })(),
    )
    t0 = time.perf_counter()
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    trainer.fit(data, num_steps=5)
    assert [e for e in seen if e[0] == "compiled"] == [("compiled",)]
    assert seen.index(("compiled",)) < seen.index(("after_step", 1))
    assert [executable.calls for executable in handed] == [5]
    with open(os.path.join(str(tmp_path), "spans.trace.json")) as f:
        events = [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]
    compiles = [e for e in events if e["name"] == "sav:fit/compile"]
    dispatches = [e for e in events if e["name"] == "sav:fit/dispatch"]
    assert len(compiles) == 1
    assert [e["args"]["step"] for e in dispatches] == [1, 2, 3, 4, 5]
    assert compiles[0]["ts"] + compiles[0]["dur"] <= dispatches[0]["ts"]
    # The compile bucket is the compile span, and nothing else.
    assert trainer.last_goodput["buckets_s"]["compile"] == pytest.approx(
        compiles[0]["dur"] / 1e6, abs=2e-3
    )
    assert [
        name for name, start, _ in spans.timeline() if start >= t0
    ].count("sav:fit/compile") == 1


def test_second_fit_continues_from_the_returned_state(tmp_path, devices):
    """The executable is per call, the state is not: 2 + 3 steps over two
    ``fit`` calls ask for the step's executable twice and log the losses
    one 5-step ``fit`` logs."""
    def losses(splits):
        trainer = _obs_trainer(
            tmp_path, diagnostics=False, trace_spans=False, log_every_steps=1
        )
        asked, compile_step = [], trainer.compile_train_step
        trainer.compile_train_step = lambda *a: asked.append(1) or compile_step(*a)
        data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
        state, out = trainer.init_state(), []
        for upto in splits:
            state, history = trainer.fit(data, num_steps=upto, state=state)
            out += [(m["step"], m["loss"]) for m in history if "loss" in m]
        assert len(asked) == len(splits)
        return out

    whole, resumed = losses([5]), losses([2, 5])
    assert [step for step, _ in whole] == [1, 2, 3, 4, 5]
    assert resumed == whole


def test_retraces_counts_the_compiles_between_two_log_boundaries(
    tmp_path, devices, monkeypatch
):
    """``retraces`` under diagnostics reads the process's compile log: a
    function compiled between two boundaries shows on the next line, and
    a quiet window reads 0."""
    import jax

    from sav_tpu.obs.fit_observers import FitObserver

    ones = jax.numpy.ones(7)

    class CompilesOnce(FitObserver):
        def after_step(self, step):
            if step == 3:  # inside the window that ends at step 4
                jax.jit(lambda x: x * 3)(ones)

    _with_observers(monkeypatch, extra=[CompilesOnce()])
    trainer = _obs_trainer(tmp_path, trace_spans=False)
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    _, history = trainer.fit(data, num_steps=6)
    assert [(m["step"], m["retraces"]) for m in history if "loss" in m] == [
        (2, 0.0), (4, 1.0), (6, 0.0),
    ]


def test_anomaly_profiler_reads_the_executable_it_was_handed(
    tmp_path, devices, monkeypatch
):
    """A capture's op index comes from the text of the executable the loop
    runs: from the first step to the exit, a capture included, the process
    lowers and compiles nothing."""
    from sav_tpu.obs import compile_log
    from sav_tpu.obs.fit_observers import FitObserver

    marks = {}

    class Marks(FitObserver):
        def first_step(self, state, batch, rng):
            marks["first_step"] = time.perf_counter()

        def exit(self, exc, state, feeder):
            marks["exit"] = time.perf_counter()

    _with_observers(monkeypatch, extra=[Marks()])
    _induce_stall_at(monkeypatch, 4)
    trainer = _obs_trainer(
        tmp_path, autoprof=True, autoprof_steps=2, autoprof_max=1,
        diagnostics=False, trace_spans=False,
    )
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    trainer.fit(data, num_steps=10)
    assert trainer.last_goodput["gauges"]["autoprof/captures"] == 1.0
    index = os.path.join(str(tmp_path), "autoprof")
    assert any("op_index.json" in files for _, _, files in os.walk(index))
    assert compile_log.log(since=marks["first_step"], until=marks["exit"]) == []


def test_unknown_device_kind_raises_before_the_loop(tmp_path, devices, monkeypatch):
    """The peak is resolved where its one reader is built: a device the
    table does not list still stops the run, from the observers'
    construction, before a batch is asked for; a stated peak trains."""
    from sav_tpu.obs import fit_observers
    from sav_tpu.utils.flops import UnknownDeviceKindError

    def table_only(override=None):
        if override:
            return float(override), "override"
        raise UnknownDeviceKindError("no peak FLOP/s for device kind 'TPU v9'")

    monkeypatch.setattr(fit_observers, "resolve_peak_flops", table_only)
    asked = []

    def data():
        for batch in fake_data_iterator(batch_size=8, image_size=32, num_classes=10):
            asked.append(1)
            yield batch

    trainer = _obs_trainer(tmp_path, diagnostics=False, trace_spans=False)
    with pytest.raises(UnknownDeviceKindError, match="TPU v9"):
        trainer.fit(data(), num_steps=2)
    assert not asked
    stated = _obs_trainer(
        tmp_path, diagnostics=False, trace_spans=False, peak_flops=1e12
    )
    _, history = stated.fit(data(), num_steps=2)
    assert history[0]["mfu"] > 0


def test_no_module_under_train_imports_the_cost_model():
    """The step loop is the lowest layer of ``train/``: how it dispatches
    depends on no FLOP peak. Read from the sources' import statements."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent / "sav_tpu" / "train"
    banned = ("sav_tpu.obs.costs", "sav_tpu.utils.flops")
    found = []
    for path in sorted(root.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    f"{node.module}.{alias.name}" for alias in node.names
                ]
            else:
                continue
            found += [(path.name, n) for n in names if n.startswith(banned)]
    assert not found, found
