"""Span tracer (sav_tpu/obs/spans.py): Chrome-trace-event JSON
well-formedness and the disabled-tracer no-op contract."""

import json
import threading

from sav_tpu.obs.spans import SpanTracer


def test_disabled_tracer_is_noop(tmp_path):
    tracer = SpanTracer(None)
    with tracer.span("anything"):
        pass
    tracer.instant("marker")
    assert tracer.write() is None
    assert not tracer.enabled


def test_trace_file_is_perfetto_loadable_json(tmp_path):
    path = str(tmp_path / "spans.trace.json")
    tracer = SpanTracer(path)
    with tracer.span("fit/batch_wait", step=1):
        pass
    with tracer.span("fit/dispatch", step=1):
        with tracer.span("inner"):
            pass
    tracer.instant("fit/stall_anomaly", step=1)
    assert tracer.write() == path

    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    complete = [e for e in events if e.get("ph") == "X"]
    assert {e["name"] for e in complete} == {
        "sav:fit/batch_wait", "sav:fit/dispatch", "sav:inner"
    }
    for e in complete:
        # The Trace Event Format's required complete-event fields.
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert e["dur"] >= 0
        assert e["ts"] >= 0
    instants = [e for e in events if e.get("ph") == "i"]
    assert instants and instants[0]["name"] == "sav:fit/stall_anomaly"
    assert instants[0]["args"] == {"step": 1}


def test_nested_span_ordering(tmp_path):
    path = str(tmp_path / "t.json")
    tracer = SpanTracer(path)
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
    tracer.write()
    with open(path) as f:
        events = {
            e["name"]: e for e in json.load(f)["traceEvents"]
            if e.get("ph") == "X"
        }
    outer, inner = events["sav:outer"], events["sav:inner"]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]


def test_span_records_on_exception(tmp_path):
    path = str(tmp_path / "t.json")
    tracer = SpanTracer(path)
    try:
        with tracer.span("failing"):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    tracer.write()
    with open(path) as f:
        names = {
            e["name"] for e in json.load(f)["traceEvents"]
            if e.get("ph") == "X"
        }
    assert "sav:failing" in names


def test_write_is_idempotent_after_midrun_exception(tmp_path):
    """Crash-path contract (ISSUE 4): a loop that flushes periodically and
    then dies mid-run leaves a valid, loadable Chrome trace — and a later
    flush (e.g. from an exception handler) is safe and wins."""
    path = str(tmp_path / "t.json")
    tracer = SpanTracer(path)
    with tracer.span("fit/dispatch", step=1):
        pass
    assert tracer.write() == path  # periodic flush mid-run
    with open(path) as f:
        first = json.load(f)["traceEvents"]
    try:
        with tracer.span("fit/dispatch", step=2):
            raise RuntimeError("mid-run crash")
    except RuntimeError:
        pass
    # Second write after the exception: still valid JSON, strictly more
    # events (the crashed span was recorded by the context manager), and
    # repeatable.
    assert tracer.write() == path
    assert tracer.write() == path
    with open(path) as f:
        doc = json.load(f)
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(events) > len([e for e in first if e.get("ph") == "X"])
    steps = {e.get("args", {}).get("step") for e in events}
    assert {1, 2} <= steps
    for e in events:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}


def test_concurrent_spans_are_thread_safe(tmp_path):
    path = str(tmp_path / "t.json")
    tracer = SpanTracer(path)

    def worker():
        for _ in range(50):
            with tracer.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tracer.write()
    with open(path) as f:
        events = [
            e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"
        ]
    assert len(events) == 200


def test_write_creates_parent_dirs(tmp_path):
    path = str(tmp_path / "deep" / "nested" / "spans.trace.json")
    tracer = SpanTracer(path)
    with tracer.span("s"):
        pass
    assert tracer.write() == path
    with open(path) as f:
        json.load(f)
