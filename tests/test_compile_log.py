"""The compile log (sav_tpu/obs/compile_log.py): one record for each trace,
lowering and backend compile jax finishes, with the persistent cache's
answer and the phase span that caused it; its summary counts every moment
of a thread once; ``fit`` and the serve engine report from it. Records and
stacks are asserted, never a clock's ratio."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from sav_tpu.data import fake_data_iterator
from sav_tpu.obs import compile_log, spans
from sav_tpu.train import TrainConfig, Trainer
from sav_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def listening():
    compile_log.listen()


def _since(t0):
    return compile_log.log(since=t0)


def _fresh(tag):
    """A function jax has not seen: its own name, its own constant."""
    def fn(x):
        return jnp.tanh(x @ x) * float(len(tag)) + jax.nn.gelu(x)

    fn.__name__ = "compile_log_" + tag
    return fn


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


# ------------------------------------------------------------------ the records


def test_listen_twice_registers_once():
    from jax._src import monitoring

    compile_log.listen()
    compile_log.listen()
    assert monitoring.get_event_listeners().count(compile_log._LOG.on_event) == 1
    assert monitoring.get_scalar_listeners().count(compile_log._LOG.on_start) == 1
    assert monitoring.get_event_duration_listeners().count(compile_log._LOG.on_duration) == 1


def test_ahead_of_time_compile_is_three_records_in_order_on_the_spans_clock():
    x = jnp.ones((16, 16))
    t0 = time.perf_counter()
    with spans.phase("test/aot"):
        jax.jit(_fresh("aot")).lower(x).compile()
    t1 = time.perf_counter()
    records = _since(t0)
    assert [(r["kind"], r["fun_name"]) for r in records] == [
        ("trace", "compile_log_aot"), ("lower", "jit(compile_log_aot)"), ("backend", "jit(compile_log_aot)"),
    ]
    assert all(t0 <= r["start"] <= r["end"] <= t1 for r in records)
    assert all(a["end"] <= b["start"] + 1e-3 for a, b in zip(records, records[1:]))
    assert {r["thread"] for r in records} == {threading.get_ident()}
    assert {r["cause"] for r in records} == {"sav:test/aot"}
    span = spans.timeline()[-1]
    assert span[0] == "sav:test/aot" and span[1] <= records[0]["start"] and records[-1]["end"] <= span[2]


def test_without_a_directory_the_cache_is_off():
    assert not jax.config.jax_compilation_cache_dir
    x = jnp.ones((8, 8))
    t0 = time.perf_counter()
    jax.jit(_fresh("off")).lower(x).compile()
    (backend,) = [r for r in _since(t0) if r["kind"] == "backend"]
    assert backend["cache"] == "off" and backend["retrieval_s"] == 0.0
    found = compile_log.summary(since=t0)
    assert (found["cache_off"], found["cache_hits"], found["cache_misses"]) == (1, 0, 0)
    assert found["backend_compile_s"] > 0.0 and found["cache_load_s"] == 0.0


def test_a_miss_then_a_hit_of_the_same_compile(tmp_path):
    fn, x = _fresh("cached"), jnp.ones((8, 8))
    assert compile_cache.enable_persistent_cache(str(tmp_path), min_compile_time_secs=0.0) == str(tmp_path)
    try:
        t0 = time.perf_counter()
        jax.jit(fn).lower(x).compile()
        # The process forgets the executable; the directory keeps it.
        jax.clear_caches()
        t1 = time.perf_counter()
        jax.jit(fn).lower(x).compile()
    finally:
        compile_cache.disable_persistent_cache()
    (miss,) = [r for r in compile_log.log(since=t0, until=t1) if r["kind"] == "backend"]
    (hit,) = [r for r in _since(t1) if r["kind"] == "backend"]
    assert miss["cache"] == "miss" and miss["retrieval_s"] == 0.0
    assert hit["cache"] == "hit" and 0.0 < hit["retrieval_s"] <= hit["end"] - hit["start"]
    assert miss["fun_name"] == hit["fun_name"] == "jit(compile_log_cached)"
    cold, warm = compile_log.summary(since=t0, until=t1), compile_log.summary(since=t1)
    assert (cold["cache_misses"], cold["cache_hits"]) == (1, 0) and cold["cache_load_s"] == 0.0
    assert (warm["cache_misses"], warm["cache_hits"]) == (0, 1) and warm["backend_compile_s"] == 0.0
    assert warm["cache_load_s"] == pytest.approx(hit["end"] - hit["start"])
    assert warm["longest_backend"][0] == {
        "fun_name": "jit(compile_log_cached)", "seconds": hit["end"] - hit["start"],
        "cache": "hit", "cause": None,
    }


def test_nested_pjit_traces_are_folded_and_counted_once():
    x = jnp.ones((4, 4))
    t0 = time.perf_counter()
    jax.jit(_fresh("nested")).lower(x)
    wall = time.perf_counter() - t0
    traces = [r for r in _since(t0) if r["kind"] == "trace"]
    # gelu and matmul are pjits of their own, traced inside this one's.
    assert [r["fun_name"] for r in traces] == ["compile_log_nested"] and traces[0]["nested"] >= 1
    found = compile_log.summary(since=t0)
    assert traces[0]["end"] - traces[0]["start"] <= found["trace_lower_s"] <= wall


def test_an_eager_op_outside_any_phase_has_no_cause():
    assert spans.open_phase() is None
    t0 = time.perf_counter()
    jnp.arange(7.0).reshape(7, 1, 1) * 3.0
    records = _since(t0)
    assert {"trace", "lower", "backend"} == {r["kind"] for r in records}
    assert {r["cause"] for r in records} == {None}
    assert set(compile_log.summary(since=t0)["by_cause"]) == {"none"}


def test_a_phase_open_on_one_thread_is_no_cause_on_another():
    seen, x = [], jnp.ones((4, 4))

    def compile_elsewhere():
        t0 = time.perf_counter()
        jax.jit(_fresh("elsewhere")).lower(x).compile()
        seen.extend(_since(t0))

    with spans.phase("test/this_thread"):
        worker = threading.Thread(target=compile_elsewhere)
        worker.start()
        worker.join(timeout=120)
        assert not worker.is_alive()
    mine = [r for r in seen if r["fun_name"].endswith("compile_log_elsewhere)")]
    assert mine and {r["cause"] for r in mine} == {None}
    assert {r["thread"] for r in mine} == {worker.ident} != {threading.get_ident()}


# ------------------------------------------------------------- the open phases


def test_phase_spans_nest_on_the_stack_and_per_step_spans_leave_it_alone():
    tracer = spans.SpanTracer(None)
    assert spans.open_phase() is None
    with spans.phase("test/outer"):
        assert spans._open.stack == ["sav:test/outer"]
        with tracer.span("fit/compile", in_timeline=True):
            assert spans.open_phase() == "sav:fit/compile"
            for step in range(3):
                with tracer.span("fit/dispatch", step=step), tracer.span("fit/batch_wait", step=step):
                    assert spans._open.stack == ["sav:test/outer", "sav:fit/compile"]
        assert spans.open_phase() == "sav:test/outer"
    assert spans._open.stack == []
    with tracer.span("fit/log_boundary"):
        assert spans.open_phase() is None


def test_a_phase_that_raises_still_leaves_the_stack():
    with pytest.raises(RuntimeError):
        with spans.phase("test/raises"):
            raise RuntimeError("boom")
    assert spans.open_phase() is None


def test_a_lazy_import_is_open_while_it_resolves(tmp_path, monkeypatch):
    package = tmp_path / "lazy_pkg_for_compile_log"
    package.mkdir()
    (package / "__init__.py").write_text(
        "from sav_tpu._lazy import install_lazy_exports\n"
        "__getattr__, __dir__ = install_lazy_exports(globals(), {}, {'heavy'})\n"
    )
    (package / "heavy.py").write_text(
        "from sav_tpu.obs import spans\n"
        "OPEN_AT_IMPORT = spans.open_phase()\n"
    )
    monkeypatch.syspath_prepend(str(tmp_path))
    import lazy_pkg_for_compile_log as pkg

    assert pkg.heavy.OPEN_AT_IMPORT == "sav:startup/import:lazy_pkg_for_compile_log.heavy"
    assert spans.open_phase() is None
    assert spans.timeline()[-1][0] == "sav:startup/import:lazy_pkg_for_compile_log.heavy"


# ------------------------------------------------------------------ the summary


def _planted(kind, start, end, *, cause=None, thread=1, cache=None, fun_name="f"):
    record = {"kind": kind, "fun_name": fun_name, "start": start, "end": end, "thread": thread, "cause": cause}
    if kind == "backend":
        record.update(cache=cache or "miss", retrieval_s=0.0)
    return record


def test_summary_counts_every_moment_once_and_splits_by_cause():
    log = compile_log.CompileLog()
    # An eager op's compile while a step is traced: inside the trace.
    log.add(_planted("trace", 1.0, 1.25, cause="sav:fit/compile", fun_name="tanh"))
    log.add(_planted("lower", 2.0, 2.5, cause="sav:fit/compile", fun_name="jit(table)"))
    log.add(_planted("backend", 2.5, 4.0, cause="sav:fit/compile", cache="miss", fun_name="jit(table)"))
    log.add(_planted("trace", 0.0, 10.0, cause="sav:fit/compile", fun_name="step"))
    log.add(_planted("lower", 10.0, 12.0, cause="sav:fit/compile", fun_name="jit(step)"))
    log.add(_planted("backend", 12.0, 32.0, cause="sav:fit/compile", cache="hit", fun_name="jit(step)"))
    # Another thread compiles meanwhile, for nobody's phase.
    log.add(_planted("backend", 3.0, 3.5, thread=2, cache="off", fun_name="jit(put)"))
    found = log.summary()
    assert found["trace_lower_s"] == pytest.approx(10.0 - 1.5 + 2.0)  # the step's less the table's compile
    assert found["backend_compile_s"] == pytest.approx(1.5 + 0.5)
    assert found["cache_load_s"] == pytest.approx(20.0)
    assert (found["cache_hits"], found["cache_misses"], found["cache_off"]) == (1, 1, 1)
    assert found["slow_compiles"] == 1  # the table's 1.5 s; 0.5 s is under jax's floor
    total = found["trace_lower_s"] + found["backend_compile_s"] + found["cache_load_s"]
    assert total == pytest.approx(32.0 + 0.5)  # one thread's 32 s and the other's half
    assert found["by_cause"]["none"]["backend_compile_s"] == pytest.approx(0.5)
    assert found["by_cause"]["sav:fit/compile"]["cache_load_s"] == pytest.approx(20.0)
    assert [r["fun_name"] for r in found["longest_backend"]] == ["jit(step)", "jit(table)", "jit(put)"]
    assert (found["records"], found["dropped"]) == (7, 0)


TRACE, LOWER, BACKEND = (
    "/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def test_a_trace_that_closes_inside_an_open_event_is_counted_not_kept():
    log = compile_log.CompileLog(maxlen=8)
    log.on_start(TRACE, 0.0, fun_name="step")
    for i in range(5000):  # more nested traces than the log holds
        log.on_start(TRACE, 0.0, fun_name="matmul")
        if i == 7:  # an eager op's compile while tracing: its trace is counted, its compile kept
            log.on_start(TRACE, 0.0, fun_name="table")
            log.on_duration(TRACE, 0.0, fun_name="table")
            log.on_start(BACKEND, 0.0, fun_name="jit(table)")
            log.on_duration(BACKEND, 0.0, fun_name="jit(table)")
        log.on_duration(TRACE, 0.0, fun_name="matmul")
    log.on_duration(TRACE, 0.0, fun_name="step")
    log.on_start(LOWER, 0.0, fun_name="jit(step)")
    log.on_start(TRACE, 0.0, fun_name="kernel_body")
    log.on_duration(TRACE, 0.0, fun_name="kernel_body")
    log.on_duration(LOWER, 0.0, fun_name="jit(step)")
    assert [(r["kind"], r["fun_name"], r.get("nested")) for r in log.log()] == [
        ("backend", "jit(table)", None), ("trace", "step", 5001), ("lower", "jit(step)", 1),
    ]
    assert log.dropped == 0 and log._thread.open == []
    # An end whose start the log never saw is a record of its own.
    log.on_duration(TRACE, 0.0, fun_name="began_before_listening")
    assert log.log()[-1]["nested"] == 0 and log._thread.open == []


def test_summary_takes_the_records_that_end_in_the_interval():
    log = compile_log.CompileLog()
    for i in range(4):
        log.add(_planted("backend", float(i), i + 0.5, cache="miss"))
    assert log.summary(until=1.5)["cache_misses"] == 2
    assert log.summary(since=2.0)["cache_misses"] == 2
    assert log.summary(since=2.0, until=3.0)["backend_compile_s"] == pytest.approx(0.5)
    assert [r["start"] for r in log.log(since=2.0, until=3.0)] == [2.0]
    assert log.summary(since=9.0)["records"] == 0


def test_the_bound_drops_the_oldest_and_the_timeline_outlives_5000_events():
    with spans.phase("test/before_the_flood"):
        pass
    kept_before = [name for name, _, _ in spans.timeline()]
    log = compile_log.CompileLog(maxlen=64)
    for i in range(5000):
        log.on_duration(BACKEND, 0.001, fun_name=f"jit(f{i})")
    assert len(log.log()) == 64 and log.dropped == 5000 - 64
    assert log.log()[-1]["fun_name"] == "jit(f4999)" and log.log()[0]["fun_name"] == "jit(f4936)"
    assert log.summary()["dropped"] == 5000 - 64
    assert [name for name, _, _ in spans.timeline()] == kept_before
    assert compile_log.LOG_MAX == 4096 and compile_log._LOG._records.maxlen == 4096


def test_events_jax_emits_beside_these_are_no_records():
    log = compile_log.CompileLog()
    log.on_event("/jax/compilation_cache/tasks_using_cache")
    log.on_duration("/jax/compilation_cache/compile_time_saved_sec", 3.0)
    log.on_duration("/jax/core/compile/some_later_event", 1.0, fun_name="f")
    assert log.log() == []


# ----------------------------------------------------------- fit and its exits


@pytest.mark.parametrize("peak, overrides", [("cpu_fake", {}), ("override", {"peak_flops": 1e12})])
def test_the_initialiser_and_the_step_name_their_phases(devices, peak, overrides):
    """Whatever the peak's source: it chooses nothing about the step's compile."""
    trainer = _toy_trainer(**overrides)
    t0 = time.perf_counter()
    state = trainer.init_state()
    t1 = time.perf_counter()
    _, history = trainer.fit(
        fake_data_iterator(batch_size=8, image_size=32, num_classes=10), num_steps=2, state=state,
    )
    init = [r for r in compile_log.log(since=t0, until=t1) if r["kind"] == "backend"]
    assert "jit(init_fn)" in [r["fun_name"] for r in init]
    assert {r["cause"] for r in init} == {"sav:trainer/init_state"}
    step = [r for r in _since(t1) if r["fun_name"] == "jit(_train_step_impl)"]
    assert [r["kind"] for r in step] == ["lower", "backend"]
    assert {r["cause"] for r in step} == {"sav:fit/compile"}
    record = history[-1]
    assert record["compile/cache_misses"] >= 1 and record["compile/cache_hits"] == 0
    assert record["compile/backend_compile_s"] > 0.0 and record["compile/trace_lower_s"] > 0.0
    assert record["compile/cache_load_s"] == 0.0
    assert trainer.last_goodput["compile"] == {k[len("compile/"):]: v for k, v in record.items()
                                               if k.startswith("compile/")}


def test_a_compile_forced_inside_the_loop_shows_by_name_with_no_cause(devices, tmp_path):
    """The step compiles once, under its phase; what still can compile
    inside the loop (here a function a log listener jits at step 4) is a
    record with its name and no cause."""
    trainer = _toy_trainer(tmp_path)
    state = trainer.init_state()
    late_fn, ones = jax.jit(_fresh("late")), jnp.ones((4, 4))

    def log_fn(m):
        if m.get("step") == 4 and "loss" in m:
            late_fn(ones)

    t0 = time.perf_counter()
    _, history = trainer.fit(
        fake_data_iterator(batch_size=8, image_size=32, num_classes=10), num_steps=5, state=state, log_fn=log_fn,
    )
    backend = [r for r in _since(t0) if r["kind"] == "backend"]
    assert [r["cause"] for r in backend if r["fun_name"] == "jit(_train_step_impl)"] == ["sav:fit/compile"]
    assert [r["cause"] for r in backend if r["fun_name"] == "jit(compile_log_late)"] == [None]
    assert history[-1]["compile/cache_misses"] >= 2
    with open(tmp_path / "goodput.json") as f:
        assert json.load(f)["compile"]["cache_misses"] == history[-1]["compile/cache_misses"]
    with open(tmp_path / "spans.trace.json") as f:
        events = [e for e in json.load(f)["traceEvents"] if e["name"].startswith("sav:compile/")]
    by_name = {e["name"]: e for e in events}
    assert by_name["sav:compile/backend:jit(_train_step_impl)"]["args"] == {"cause": "sav:fit/compile", "cache": "off"}
    late = by_name["sav:compile/backend:jit(compile_log_late)"]
    assert late["args"] == {"cause": None, "cache": "off"}
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in events)
    # On the spans' clock: the late compile lies inside the log_fn span of its thread.
    with open(tmp_path / "spans.trace.json") as f:
        listeners = [e for e in json.load(f)["traceEvents"] if e["name"] == "sav:fit/log_fn"]
    assert any(d["tid"] == late["tid"] and d["ts"] <= late["ts"] and late["ts"] + late["dur"] <= d["ts"] + d["dur"]
               for d in listeners)


def test_the_log_imports_without_jax():
    script = (
        "import sys\n"
        "import sav_tpu.obs.compile_log as compile_log, sav_tpu.obs.spans\n"
        "from sav_tpu.obs import compile_log as again\n"
        "assert again is compile_log and compile_log.summary()['records'] == 0\n"
        "assert 'jax' not in sys.modules, 'the no-jax import contract'\n"
        "compile_log.listen()\n"
        "assert 'jax' in sys.modules\n"
    )
    done = subprocess.run([sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
                          timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
