"""The program's timeline (sav_tpu/obs/spans.py): spans reach a running
profiler session under their ``sav:`` names from any thread, cost nothing
observable without one, feed the goodput ledger from the same ``with``;
phase spans enter the bounded process timeline, per-step spans never do;
``fit``'s log boundary nests its three children; the step's four named
scopes reach the compiled program's ``op_name`` metadata. Events and calls
are counted; no assertion rests on a wall-clock ratio."""

import glob
import json
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from sav_tpu.data import fake_data_iterator
from sav_tpu.data.feeder import DeviceFeeder
from sav_tpu.obs import spans
from sav_tpu.obs.goodput import GoodputLedger
from sav_tpu.train import TrainConfig, Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_trainer(tmp_path=None, **overrides):
    from sav_tpu.models import create_model

    fields = dict(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        compute_dtype="float32", global_batch_size=8, num_train_images=8 * 64,
        num_epochs=1, warmup_epochs=0, lr_scaling_divisor=8,
        transpose_images=False, log_every_steps=2, seed=0,
        log_dir=str(tmp_path) if tmp_path is not None else None,
        trace_spans=tmp_path is not None,
    )
    fields.update(overrides)
    config = TrainConfig(**fields)
    model = create_model(
        config.model_name, num_classes=10, dtype=jnp.float32,
        num_layers=1, embed_dim=32, num_heads=2,
    )
    return Trainer(config, model=model)


def _host_events(profile_dir):
    """``[(thread line, event name)]`` of the ``sav:`` events in the newest
    trace's ``/host:CPU`` plane."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            found += [(i, ev.name) for ev in line.events if ev.name.startswith(spans.PREFIX)]
    return found


def _start_trace(profile_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(profile_dir), profiler_options=options)


# ------------------------------------------------------------ profiler's clock


def test_spans_from_two_threads_land_in_the_host_plane(tmp_path):
    tracer = spans.SpanTracer(None)
    _start_trace(tmp_path)
    try:
        with tracer.span("fit/dispatch", step=1):
            jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
        worker = threading.Thread(target=lambda: tracer.span("feeder/fetch").__enter__().__exit__(None, None, None))
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    by_name = {name: line for line, name in events}
    assert set(by_name) == {"sav:fit/dispatch", "sav:feeder/fetch"}
    assert by_name["sav:fit/dispatch"] != by_name["sav:feeder/fetch"], "one line a thread"


def test_without_a_session_a_span_records_nothing_and_raises_nothing(tmp_path):
    tracer = spans.SpanTracer(None)
    for step in range(100):
        with tracer.span("fit/before_any_session", step=step):
            pass
    assert tracer.num_events == 0 and tracer.write() is None
    _start_trace(tmp_path)
    try:
        with tracer.span("fit/inside_the_session"):
            pass
    finally:
        jax.profiler.stop_trace()
    with tracer.span("fit/after_the_session"):
        pass
    assert [name for _, name in _host_events(tmp_path)] == ["sav:fit/inside_the_session"]


def test_a_span_open_when_the_session_starts_is_left_out(tmp_path):
    # The benchmark starts and stops its profiler from inside fit's log_fn
    # span: the boundary that holds the start is not in the trace.
    tracer = spans.SpanTracer(None)
    with tracer.span("fit/log_boundary"):
        _start_trace(tmp_path)
    try:
        with tracer.span("fit/log_boundary"):
            with tracer.span("fit/log_sync"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert sorted(name for _, name in _host_events(tmp_path)) == ["sav:fit/log_boundary", "sav:fit/log_sync"]


# --------------------------------------------------------- one with, three sinks


def test_one_span_feeds_the_ledger_and_the_chrome_file(tmp_path):
    ticks = iter(range(100))
    ledger = GoodputLedger(clock=lambda: float(next(ticks)))
    tracer = spans.SpanTracer(str(tmp_path / "spans.trace.json"), ledger=ledger)
    with tracer.span("fit/batch_wait", bucket="input_wait", step=7) as span:
        pass
    assert ledger.bucket_seconds("input_wait") == span.seconds >= 0.0
    tracer.write()
    with open(tmp_path / "spans.trace.json") as f:
        (event,) = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert event["name"] == "sav:fit/batch_wait" and event["args"] == {"step": 7}
    assert event["dur"] == pytest.approx(span.seconds * 1e6)
    assert event["ts"] == pytest.approx(span.start * 1e6)  # perf_counter, no private zero


def test_a_bucket_needs_a_ledger_and_a_known_name():
    with pytest.raises(ValueError, match="no ledger"):
        spans.SpanTracer(None).span("fit/eval", bucket="eval")
    tracer = spans.SpanTracer(None, ledger=GoodputLedger())
    with pytest.raises(KeyError, match="unknown goodput bucket"):
        with tracer.span("fit/eval", bucket="no_such_bucket"):
            pass


def test_span_books_its_bucket_when_the_body_raises():
    booked = []

    class Ledger:
        def account(self, bucket, seconds):
            booked.append(bucket)

    tracer = spans.SpanTracer(None, ledger=Ledger())
    with pytest.raises(RuntimeError):
        with tracer.span("fit/checkpoint", bucket="checkpoint"):
            raise RuntimeError("disk full")
    assert booked == ["checkpoint"]


def test_feeder_emits_fetch_and_place_from_its_own_thread(tmp_path):
    tracer = spans.SpanTracer(str(tmp_path / "t.json"))
    batches = [{"x": i} for i in range(5)]
    with DeviceFeeder(iter(batches), lambda b: b, depth=2, tracer=tracer) as feeder:
        assert [b["x"] for b in feeder] == [0, 1, 2, 3, 4]
        stats = feeder.stats()
    tracer.write()
    with open(tmp_path / "t.json") as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in events]
    # Six fetches: the sixth met StopIteration.
    assert names.count("sav:feeder/fetch") == 6 and names.count("sav:feeder/place") == 5
    assert {e["tid"] for e in events} != {threading.get_ident()}
    placed = sum(e["dur"] for e in events if e["name"] == "sav:feeder/place") * 1e-6
    assert stats["h2d_s"] == pytest.approx(placed, abs=1e-5)  # one measurement, two readers


# ------------------------------------------------------------ process timeline


def test_timeline_keeps_phase_spans_only_and_is_bounded():
    before = len(spans.timeline())
    tracer = spans.SpanTracer(None)
    with tracer.span("fit/dispatch", step=1):
        pass
    assert len(spans.timeline()) == before
    for i in range(spans.TIMELINE_MAX + 50):
        with spans.phase(f"test/bounded:{i}"):
            pass
    kept = spans.timeline()
    assert len(kept) == spans.TIMELINE_MAX
    assert kept[-1][0] == f"sav:test/bounded:{spans.TIMELINE_MAX + 49}"
    assert all(start <= end for _, start, end in kept)


def test_in_phase_decorates_a_function():
    @spans.in_phase("test/decorated")
    def build(x, *, y):
        """doc"""
        return x + y

    assert build(1, y=2) == 3 and build.__doc__ == "doc"
    assert spans.timeline()[-1][0] == "sav:test/decorated"


def test_fit_of_30_steps_leaves_no_per_step_span_in_the_timeline(devices):
    trainer = _toy_trainer()
    state = trainer.init_state()
    mark = ("sav:test/fit_mark", 0.0, 0.0)
    with spans.phase("test/fit_mark"):
        pass
    trainer.fit(fake_data_iterator(batch_size=8, image_size=32, num_classes=10),
                num_steps=30, state=state)
    names = [name for name, _, _ in spans.timeline()]
    since = names[len(names) - names[::-1].index(mark[0]):]
    assert since == ["sav:fit/compile"], since
    assert len(names) <= spans.TIMELINE_MAX


def test_trainer_construction_and_state_are_phases(devices):
    with spans.phase("test/trainer_mark"):
        pass
    trainer = _toy_trainer()
    trainer.init_state()
    names = [name for name, _, _ in spans.timeline()]
    since = names[len(names) - names[::-1].index("sav:test/trainer_mark"):]
    assert since == ["sav:trainer/init", "sav:trainer/init_state"]


def test_lazy_imports_are_timed_once_and_outermost_only():
    script = (
        "import sys, json\n"
        "import sav_tpu.train.supervisor, sav_tpu.obs.spans, sav_tpu.data.feeder\n"
        "assert 'jax' not in sys.modules, 'the no-jax import contract'\n"
        "from sav_tpu.obs import spans\n"
        "with spans.SpanTracer(None).span('supervisor/no_jax'): pass\n"
        "assert 'jax' not in sys.modules, 'a span pulled jax in'\n"
        "from sav_tpu.train import TrainConfig, Trainer\n"
        "from sav_tpu.train import Trainer as again\n"
        "from sav_tpu.models import create_model\n"
        "assert 'orbax' not in sys.modules, 'orbax belongs to the first Checkpointer'\n"
        "print(json.dumps(spans.timeline()))\n"
    )
    done = subprocess.run([sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
                          timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    timeline = json.loads(done.stdout.splitlines()[-1])
    names = [name for name, _, _ in timeline]
    # trainer.py imports sav_tpu.models itself: inside the trainer's span,
    # not a span of its own, and not again when asked for afterwards.
    assert names == [
        "sav:startup/import:sav_tpu.train.config",
        "sav:startup/import:sav_tpu.train.trainer",
    ]
    assert all(end >= start for _, start, end in timeline)


# ------------------------------------------------------------------ fit's spans


@pytest.fixture(scope="module")
def toy_fit_events(tmp_path_factory, devices):
    tmp_path = tmp_path_factory.mktemp("toy_fit")
    trainer = _toy_trainer(tmp_path)
    seen = []
    trainer.fit(fake_data_iterator(batch_size=8, image_size=32, num_classes=10),
                num_steps=6, state=trainer.init_state(), log_fn=seen.append)
    with open(tmp_path / "spans.trace.json") as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    return events, seen


def test_log_boundary_holds_its_three_children(toy_fit_events):
    events, seen = toy_fit_events
    boundaries = [e for e in events if e["name"] == "sav:fit/log_boundary"]
    assert len(boundaries) == 3  # 6 steps, a boundary every 2
    assert len([m for m in seen if "loss" in m]) == 3
    for boundary in boundaries:
        inside = [
            e["name"] for e in sorted(events, key=lambda e: e["ts"])
            if e is not boundary and e["tid"] == boundary["tid"]
            and e["ts"] >= boundary["ts"] and e["ts"] + e["dur"] <= boundary["ts"] + boundary["dur"]
        ]
        assert inside == ["sav:fit/log_sync", "sav:fit/log_host", "sav:fit/log_fn"]


def test_each_step_has_its_wait_and_its_dispatch(toy_fit_events):
    events, _ = toy_fit_events
    count = {name: sum(e["name"] == name for e in events) for name in {e["name"] for e in events}}
    assert count["sav:fit/batch_wait"] == 6
    # One compile, at the first batch; every step is a dispatch.
    assert count["sav:fit/compile"] == 1 and count["sav:fit/dispatch"] == 6
    # feed_depth 2: the step three back is waited for from the fourth on.
    assert count["sav:fit/run_ahead_wait"] == 3
    assert count["sav:feeder/place"] >= 6
    assert "sav:fit/shard_batch" not in count
    train_tid = next(e["tid"] for e in events if e["name"] == "sav:fit/dispatch")
    assert all(e["tid"] != train_tid for e in events if e["name"].startswith("sav:feeder/"))


def test_no_phase_is_spanned_twice(toy_fit_events):
    """One ``with`` a phase: no two spans of one thread share their start."""
    events, _ = toy_fit_events
    starts = [(e["tid"], e["ts"]) for e in events]
    assert len(starts) == len(set(starts))


# ---------------------------------------------------------------- named scopes


def _scopes(name: str) -> list:
    """An ``op_name``'s components with the transforms' wrappers taken off
    (``transpose(jvp(loss))`` -> ``loss``)."""
    out = []
    for part in name.split("/"):
        while True:
            inner = re.fullmatch(r"\w+\((.*)\)", part)
            if not inner:
                break
            part = inner.group(1)
        out.append(part)
    return out


@pytest.fixture(scope="module")
def step_op_names(devices):
    trainer = _toy_trainer(device_preprocess=True, diagnostics=True)
    state = trainer.init_state()
    batch = trainer.shard_batch({
        "images": jnp.zeros((8, 32, 32, 3), jnp.uint8), "labels": jnp.zeros((8,), jnp.int32),
    })
    text = trainer.compile_train_step(state, batch, jax.random.PRNGKey(0)).as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope", ["preprocess", "loss", "optimizer", "metrics"])
def test_the_step_names_its_unowned_device_time(step_op_names, scope):
    assert any(scope in _scopes(name) for name in step_op_names), scope


def test_no_scope_wraps_the_model(step_op_names):
    model_ops = [name for name in step_op_names if "SelfAttentionBlock" in name]
    assert model_ops
    for name in model_ops:
        assert not {"preprocess", "loss", "optimizer", "metrics"} & set(_scopes(name)), name


def test_eval_step_shares_the_scopes(devices):
    trainer = _toy_trainer()
    state = trainer.init_state()
    batch = {"images": jnp.zeros((8, 32, 32, 3), jnp.float32), "labels": jnp.zeros((8,), jnp.int32)}
    text = trainer._eval_step.lower(state, batch).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("metrics" in _scopes(n) for n in names)
