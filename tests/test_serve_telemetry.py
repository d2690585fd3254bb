"""Serve telemetry (sav_tpu/serve/telemetry.py) — ISSUE 11.

Unit tier (stdlib-only, no jax): span stamping under a fake clock
(every request's stamps monotone and lifecycle-ordered), the
sliding-window sketch against the exact percentile, the live window's
graceful empty-state, the ledger's windowed rebase (final summary
bit-identical with the window on or off), SLO burn-window arithmetic
pins, the chrome-trace export round-tripped through ``obs/traceview``,
serve heartbeat schema + offline aggregation, and the structural
zero-sync proof that the batcher/telemetry import surface never pulls
in jax.

Engine tier (tiny ViT on CPU): complete 8-stage span timelines on real
requests, the live-stats view before the first completed batch (no
IndexError — the bugfix satellite), the induced-latency-spike e2e
(slow-request exemplar naming the stage that ate the latency + exactly
one bounded anomaly capture), the telemetry-on/off throughput A/B
(within 2%), and the ``serve_status`` / ``run_report --serve`` /
sentinel ``slo_hit_frac`` surfaces.
"""

import gzip
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from sav_tpu.obs import traceview
from sav_tpu.serve.batcher import DynamicBatcher
from sav_tpu.serve.bucketing import BucketLadder
from sav_tpu.serve.latency import LatencyLedger, percentile
from sav_tpu.serve.telemetry import (
    INTERVALS,
    STAGES,
    LiveWindow,
    RequestTrace,
    ServeTelemetry,
    SlidingWindow,
    SLOTracker,
    SpanRing,
    aggregate_serve,
    dominant_stage,
    export_chrome_trace,
    find_exemplars,
    intervals,
    stamp,
    trace_record,
    write_request_trace,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "sentinel_fixtures")


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------- span tier


def test_stamp_intervals_and_dominant_stage():
    clock = FakeClock()
    trace = RequestTrace(7, 0.1, clock())
    walk = [
        ("admit", 0.001), ("batch_formed", 0.004), ("placed", 0.005),
        ("dispatched", 0.006), ("executed", 0.030), ("depadded", 0.0305),
        ("completed", 0.031),
    ]
    for stage, t in walk:
        stamp(trace, stage, t)
    stages = intervals(trace.stamps)
    assert stages["admission"] == pytest.approx(0.001)
    assert stages["queue"] == pytest.approx(0.003)
    assert stages["device"] == pytest.approx(0.024)
    assert dominant_stage(stages) == "device"
    # Every lifecycle interval is derivable from a full walk.
    assert set(stages) == {name for name, _, _ in INTERVALS}
    # stamp() on an untraced request is a no-op, never an error.
    stamp(None, "admit", 1.0)
    rec = trace_record(
        trace, latency_s=0.031, overrun_s=-0.069, bucket=4, batch_n=3
    )
    assert rec["rid"] == 7
    assert rec["hit"] is True
    assert rec["dominant_stage"] == "device"
    assert rec["stages_ms"]["device"] == pytest.approx(24.0)


def test_batcher_stamps_spans_under_fake_clock():
    """The drain's span contract, deterministically: submit -> admit ->
    batch_formed stamps appear in lifecycle order, monotone in the fake
    clock, and batch_formed carries the SAME instant for every request
    in the batch (one clock read per formed batch)."""
    clock = FakeClock()
    telemetry = ServeTelemetry(clock=clock)
    batcher = DynamicBatcher(
        BucketLadder([1, 2]), step_time_fn=lambda b: 0.0,
        default_deadline_s=1.0, clock=clock,
    )
    traces = []
    for _ in range(2):
        trace = telemetry.begin_trace(1.0)
        traces.append(trace)
        batcher.submit("x", trace=trace)
        clock.advance(0.01)
    formed = batcher.next_batch()
    assert len(formed.requests) == 2
    for trace in traces:
        names = [s for s, _ in trace.stamps]
        assert names == ["submit", "admit", "batch_formed"]
        times = [t for _, t in trace.stamps]
        assert times == sorted(times)
    formed_ts = {t for trace in traces for s, t in trace.stamps
                 if s == "batch_formed"}
    assert len(formed_ts) == 1
    assert formed_ts == {formed.formed_t}
    batcher.close()


def test_span_ring_bounded():
    ring = SpanRing(3)
    for i in range(10):
        ring.append({"rid": i})
    assert len(ring) == 3
    assert ring.appended == 10
    assert [r["rid"] for r in ring.records()] == [7, 8, 9]
    with pytest.raises(ValueError):
        SpanRing(0)


def test_chrome_export_roundtrips_through_traceview(tmp_path):
    """The golden request-trace round trip: a deterministic ring ->
    chrome events -> *.trace.json.gz -> traceview.load_trace +
    request_spans, with stage durations pinned — request timelines read
    through the same machinery as device profiles."""
    clock = FakeClock(10.0)
    trace = RequestTrace(3, 0.05, clock())
    for stage, t in [
        ("admit", 10.001), ("batch_formed", 10.002), ("placed", 10.003),
        ("dispatched", 10.004), ("executed", 10.024),
        ("depadded", 10.0245), ("completed", 10.025),
    ]:
        stamp(trace, stage, t)
    rec = trace_record(
        trace, latency_s=0.025, overrun_s=-0.025, bucket=2, batch_n=2
    )
    doc = export_chrome_trace([rec])
    names = [e.get("name") for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert names == [name for name, _, _ in INTERVALS]
    path = str(tmp_path / "serve_traces" / "requests.trace.json.gz")
    assert write_request_trace(path, [rec]) == path
    # traceview's discovery + loader find and parse it like any capture.
    assert traceview.find_traces(str(tmp_path)) == [path]
    events = traceview.load_trace(path)
    spans = traceview.request_spans(events)
    assert set(spans) == {3}
    view = spans[3]
    assert view["bucket"] == 2
    assert view["dominant_stage"] == "device"
    stages = {name: dur for name, _, dur in view["stages"]}
    assert stages["device"] == pytest.approx(20.0, abs=0.01)
    assert stages["queue"] == pytest.approx(1.0, abs=0.01)
    assert view["total_ms"] == pytest.approx(25.0, abs=0.1)
    # A device-profile trace has no request plane: empty, not an error.
    assert traceview.request_spans(
        [{"ph": "X", "name": "fusion.1", "ts": 0, "dur": 5}]
    ) == {}


def test_trace_report_renders_request_timelines(tmp_path):
    clock = FakeClock(0.0)
    trace = RequestTrace(1, 0.01, clock())
    for stage, t in [
        ("admit", 0.001), ("batch_formed", 0.002), ("placed", 0.003),
        ("dispatched", 0.004), ("executed", 0.030), ("depadded", 0.031),
        ("completed", 0.032),
    ]:
        stamp(trace, stage, t)
    rec = trace_record(
        trace, latency_s=0.032, overrun_s=0.022, bucket=1, batch_n=1
    )
    write_request_trace(
        str(tmp_path / "requests.trace.json.gz"), [rec]
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    assert "serve request timelines: 1 request(s)" in proc.stdout
    assert "OVERRAN deadline by 22.0 ms — device dominated" in proc.stdout


# ----------------------------------------------------------- window tier


def test_sliding_window_matches_exact_percentile_under_cap():
    clock = FakeClock()
    window = SlidingWindow(10.0, max_samples=1024, clock=clock)
    rng = np.random.default_rng(0)
    values = [float(v) for v in rng.uniform(1.0, 50.0, 200)]
    for v in values:
        window.observe(v)
        clock.advance(0.01)
    # Everything fits in both the time window and the cap: EXACT.
    for q in (50.0, 95.0, 99.0):
        assert window.percentile(q) == percentile(sorted(values), q)


def test_sliding_window_time_eviction_and_cap_tolerance():
    clock = FakeClock()
    window = SlidingWindow(1.0, max_samples=64, clock=clock)
    for v in (100.0, 200.0):
        window.observe(v)
    clock.advance(2.0)  # both now stale
    assert window.percentile(99.0) is None
    assert window.count() == 0
    # Over the cap: percentiles are exact over the newest max_samples —
    # the bounded-staleness approximation, pinned against the exact
    # tail.
    values = [float(i) for i in range(200)]
    for v in values:
        window.observe(v)
    retained = values[-64:]
    assert window.count() == 64
    assert window.percentile(50.0) == percentile(retained, 50.0)
    with pytest.raises(ValueError):
        SlidingWindow(0.0)
    with pytest.raises(ValueError):
        SlidingWindow(1.0, max_samples=0)


def test_live_window_graceful_before_first_batch_then_exact():
    """The bugfix satellite's unit half: a live snapshot before any
    completed batch is all Nones/zeros — never an IndexError."""
    clock = FakeClock()
    window = LiveWindow(30.0, clock=clock)
    empty = window.snapshot()
    assert empty["requests"] == 0
    assert empty["p50_ms"] is None
    assert empty["p99_ms"] is None
    assert empty["occupancy"] is None
    assert empty["throughput_rps"] == 0.0
    window.observe_window(
        latencies_s=[0.010, 0.020, 0.030], overruns_s=[-0.1, -0.1, 0.002],
        bucket=4, queue_depth=5, step_s=0.008,
    )
    clock.advance(1.0)
    window.observe_shed(2)
    snap = window.snapshot()
    assert snap["requests"] == 3
    assert snap["batches"] == 1
    assert snap["p50_ms"] == 20.0
    assert snap["queue_depth_max"] == 5
    assert snap["occupancy"] == 0.75
    assert snap["padding_waste_frac"] == 0.25
    assert snap["overruns"] == 1
    assert snap["shed"] == 2
    # Time passes beyond the window: everything ages out gracefully.
    clock.advance(60.0)
    aged = window.snapshot()
    assert aged["requests"] == 0 and aged["p99_ms"] is None


def test_ledger_windowed_rebase_final_summary_bit_identical():
    """The acceptance pin: the ledger's FINAL numbers are bit-identical
    with the live window attached or not — same observation stream,
    byte-equal summary()/flat_metrics()."""
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    plain = LatencyLedger(clock=clock)
    windowed = LatencyLedger(
        clock=clock, window=LiveWindow(30.0, clock=clock)
    )
    for ledger in (plain, windowed):
        ledger.start()
    stream = [
        dict(bucket=4, latencies_s=[0.01, 0.02, 0.03],
             overruns_s=[-0.05, -0.04, 0.002], queue_depth=5, step_s=0.008),
        dict(bucket=1, latencies_s=[0.04], overruns_s=[-0.1],
             queue_depth=0, step_s=0.004),
    ]
    for i, obs in enumerate(stream):
        t[0] = float(i + 1)
        for ledger in (plain, windowed):
            ledger.observe_batch(**obs)
    for ledger in (plain, windowed):
        ledger.observe_rejected(2)
    assert plain.summary() == windowed.summary()
    assert plain.flat_metrics() == windowed.flat_metrics()
    assert json.dumps(plain.summary(), sort_keys=True) == json.dumps(
        windowed.summary(), sort_keys=True
    )
    # Only the windowed one has a live view; the plain one says so.
    assert plain.live() is None
    assert windowed.live()["requests"] == 4


# -------------------------------------------------------------- SLO tier


def test_slo_burn_window_arithmetic_pins():
    clock = FakeClock()
    slo = SLOTracker(
        target=0.9, fast_window_s=10.0, slow_window_s=100.0,
        burn_threshold=2.0, clock=clock,
    )
    # Empty: no burn, no alert, hit_frac None (not 1.0, not 0.0).
    state = slo.state()
    assert state["burn_fast"] is None and state["burn_rate"] is None
    assert state["hit_frac"] is None and state["burning"] is False
    # 9 hits + 1 miss: miss_frac 0.1 == the 0.1 budget -> burn 1.0.
    for i in range(10):
        slo.observe_request(i != 0)
        clock.advance(0.1)
    state = slo.state()
    assert state["hit_frac"] == pytest.approx(0.9)
    assert state["burn_fast"] == pytest.approx(1.0)
    assert state["burn_slow"] == pytest.approx(1.0)
    assert state["burning"] is False  # burn 1.0 <= threshold 2.0
    # A miss storm: 5 misses in a row -> fast window burns hot.
    for _ in range(5):
        slo.observe_request(False)
        clock.advance(0.1)
    state = slo.state()
    assert state["burn_fast"] == pytest.approx((6 / 15) / 0.1)
    assert state["burning"] is True  # both windows past the threshold
    # The fast window forgets; the slow window remembers: after 20s of
    # clean traffic the fast burn is back to 0 but the slow one still
    # carries the storm — the two-window AND stops alerting (recovered),
    # while burn_rate (slow) still reports the budget spend.
    for _ in range(200):
        slo.observe_request(True)
        clock.advance(0.1)
    state = slo.state()
    assert state["burn_fast"] == 0.0
    assert state["burn_slow"] > 0.0
    assert state["burning"] is False
    assert state["burn_rate"] == state["burn_slow"]
    assert state["requests"] == 215 and state["misses"] == 6


def test_slo_validation():
    with pytest.raises(ValueError, match="target"):
        SLOTracker(target=1.0)
    with pytest.raises(ValueError, match="shorter"):
        SLOTracker(fast_window_s=60.0, slow_window_s=60.0)


def test_shed_requests_count_as_slo_misses():
    clock = FakeClock()
    telemetry = ServeTelemetry(clock=clock)
    telemetry.observe_shed(3)
    state = telemetry.slo.state()
    assert state["requests"] == 3 and state["misses"] == 3
    assert telemetry.stats()["shed"] == 3.0


# -------------------------------------------------- heartbeats + offline


def _write_serve_beats(log_dir, proc, payloads):
    from sav_tpu.obs.fleet import HeartbeatWriter

    writer = HeartbeatWriter(str(log_dir), process_index=proc,
                             process_count=2)
    for payload in payloads:
        writer.serve_beat(payload)
    writer.close("ok")


def _beat(requests, p99, queue, rps, *, burning=False, shed=0):
    return {
        "up_s": 12.0,
        "requests": requests,
        "batches": requests,
        "shed": shed,
        "queued": queue,
        "inflight": 1,
        "w": {
            "window_s": 30.0, "requests": requests, "p50_ms": p99 / 2,
            "p95_ms": p99 * 0.9, "p99_ms": p99, "throughput_rps": rps,
            "queue_depth_last": queue, "queue_depth_avg": queue,
            "queue_depth_max": queue, "occupancy": 0.9,
            "padding_waste_frac": 0.1, "overruns": 0, "shed": shed,
        },
        "slo": {
            "target": 0.99, "hit_frac": 0.97 if burning else 0.999,
            "burn_fast": 5.0 if burning else 0.1,
            "burn_slow": 3.0 if burning else 0.1,
            "burn_rate": 3.0 if burning else 0.1,
            "burning": burning,
        },
        "exemplars": 1 if burning else 0,
    }


def test_serve_heartbeat_schema_and_aggregation(tmp_path):
    """kind=serve lines ride the PR-7 fleet substrate and aggregate to
    the per-replica router view: p99 / queue / occupancy / SLO burn per
    replica plus fleet totals."""
    _write_serve_beats(
        tmp_path, 0, [_beat(40, 20.0, 2, 100.0), _beat(80, 21.0, 3, 110.0)]
    )
    _write_serve_beats(
        tmp_path, 1,
        [_beat(35, 30.0, 9, 90.0), _beat(70, 45.0, 12, 80.0, burning=True,
                                         shed=5)],
    )
    # The raw lines carry the schema contract.
    with open(tmp_path / "fleet" / "proc_0.jsonl") as f:
        first = json.loads(f.readline())
    assert first["kind"] == "serve"
    assert first["proc"] == 0 and first["procs"] == 2
    assert first["w"]["p99_ms"] == 20.0
    assert first["slo"]["target"] == 0.99
    assert "t" in first and "host" in first and "pid" in first
    summary = aggregate_serve(str(tmp_path))
    replicas = summary["replicas"]
    assert set(replicas) == {"0", "1"}
    assert replicas["0"]["p99_ms"] == 21.0
    assert replicas["0"]["queue_depth"] == 3
    assert replicas["0"]["occupancy"] == 0.9
    assert replicas["0"]["burning"] is False
    assert replicas["1"]["burning"] is True
    assert replicas["1"]["shed"] == 5
    assert replicas["1"]["median_p99_ms"] == pytest.approx(37.5)
    fleet = summary["fleet"]
    assert fleet["replicas"] == 2
    assert fleet["throughput_rps"] == pytest.approx(190.0)
    assert fleet["worst_p99_ms"] == 45.0
    assert fleet["burning"] == [1]
    assert len(summary["timeline"]) == 4
    # Training-heartbeat-only dirs aggregate to no replicas.
    assert aggregate_serve(str(tmp_path / "nothing"))["replicas"] == {}


def test_capacity_stamp_and_alert_rules_ride_the_beat(
    tmp_path, monkeypatch
):
    """ISSUE 19: a replica with a measured step publishes
    ``capacity_rps`` (max_batch / step_s_avg) in every beat, evaluates
    the armed alert rules at beat cadence (built-in SLO rule + the
    SAV_ALERT_RULES env seam), stamps active rule names on the line,
    and resolves open episodes at close."""
    from sav_tpu.obs.alerts import episodes, read_alerts
    from sav_tpu.obs.fleet import HeartbeatWriter

    rules_path = tmp_path / "rules.json"
    rules_path.write_text(json.dumps({"rules": [{
        "name": "hot-p99", "severity": "warn",
        "when": [{"metric": "w.p99_ms", "op": ">", "value": 40.0}],
    }]}))
    monkeypatch.setenv("SAV_ALERT_RULES", str(rules_path))
    clock = FakeClock(100.0)
    writer = HeartbeatWriter(str(tmp_path), process_index=0, clock=clock)
    telemetry = ServeTelemetry(
        str(tmp_path), clock=clock, wall_clock=clock, writer=writer,
        max_batch=8, heartbeat_secs=0.0,
    )
    # Armed rule set: the built-in SLO burn rule, the quality set
    # (ISSUE 20 — armed alongside, never inside default_rules), then
    # the env rule.
    from sav_tpu.obs.alerts import quality_rules

    assert [r.name for r in telemetry.alerts.rules] == (
        ["slo-burn"] + [r.name for r in quality_rules()] + ["hot-p99"]
    )
    # A measured 20 ms step at max_batch 8 -> 400 rows/s capacity.
    telemetry.window.observe_window(
        latencies_s=[0.08], overruns_s=[], bucket=8, queue_depth=1,
        step_s=0.02,
    )
    telemetry.serve_beat()
    with open(tmp_path / "fleet" / "proc_0.jsonl") as f:
        beat = json.loads(f.readline())
    assert beat["capacity_rps"] == pytest.approx(400.0)
    # 80 ms latency > 40 ms rule threshold: firing, stamped on the line.
    assert beat["alerts"] == ["hot-p99"]
    # Close resolves the open episode (the emitter outlives no episode).
    summary = telemetry.close("ok")
    events = read_alerts(str(tmp_path))
    assert [(e["rule"], e["event"]) for e in events] == [
        ("hot-p99", "firing"), ("hot-p99", "resolved"),
    ]
    assert episodes(events)["hot-p99"]["active"] is False
    assert summary["alerts"]["episodes"] == {"hot-p99": 1}
    # No writer -> no engine armed; nothing to evaluate, nothing breaks.
    bare = ServeTelemetry(clock=FakeClock())
    assert bare.alerts is None


def test_capacity_absent_without_measured_step(tmp_path):
    """Skip-not-zero-fill: no measured step (or no max_batch) means NO
    capacity_rps key — the fold must never read an unmeasured replica
    as zero capacity."""
    from sav_tpu.obs.fleet import HeartbeatWriter

    clock = FakeClock(10.0)
    writer = HeartbeatWriter(str(tmp_path), process_index=0, clock=clock)
    telemetry = ServeTelemetry(
        str(tmp_path), clock=clock, wall_clock=clock, writer=writer,
        max_batch=8,
    )
    telemetry.serve_beat()  # window empty: step_s_avg is None
    with open(tmp_path / "fleet" / "proc_0.jsonl") as f:
        beat = json.loads(f.readline())
    assert "capacity_rps" not in beat
    telemetry.close("ok")


def test_fleet_status_renders_serve_replicas(tmp_path):
    _write_serve_beats(tmp_path, 0, [_beat(40, 20.0, 2, 100.0)])
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_status.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    assert "Serve replicas: 1" in proc.stdout
    assert "replica 0: p99 20.0 ms" in proc.stdout
    as_json = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_status.py"),
         "--json", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    doc = json.loads(as_json.stdout)
    assert doc["serve"]["replicas"]["0"]["p99_ms"] == 20.0


def test_serve_status_cli_live_dir_and_exit_codes(tmp_path):
    """The mid-run observability acceptance: serve_status on a LIVE log
    dir (heartbeats flowing, manifest still 'running') reports windowed
    p99/queue/occupancy from artifacts alone; exit 2 on a bad dir."""
    _write_serve_beats(
        tmp_path, 0, [_beat(40, 20.0, 2, 100.0), _beat(80, 22.5, 4, 105.0)]
    )
    # A live (unfinalized) manifest — the process is still serving.
    with open(tmp_path / "manifest-serve-live.json", "w") as f:
        json.dump({"schema": 1, "kind": "serve", "outcome": "running",
                   "notes": {}, "metrics": {}}, f)
    # One slow-request exemplar bundle.
    os.makedirs(tmp_path / "serve_traces")
    with open(tmp_path / "serve_traces" / "slow_0000_req9.json", "w") as f:
        json.dump({
            "schema": 1, "kind": "slow_exemplar", "rid": 9,
            "latency_ms": 180.0, "deadline_ms": 100.0, "overrun_ms": 80.0,
            "dominant_stage": "queue",
            "stages_ms": {"queue": 150.0, "device": 25.0},
        }, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_status.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    assert "p99 22.5 ms" in proc.stdout
    assert "queue 4" in proc.stdout
    assert "occupancy 90%" in proc.stdout
    assert "outcome=running" in proc.stdout and "live" in proc.stdout
    assert "queue dominated" in proc.stdout
    as_json = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_status.py"),
         "--json", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    doc = json.loads(as_json.stdout)
    assert doc["replicas"]["0"]["p99_ms"] == 22.5
    assert len(doc["exemplars"]) == 1
    bad = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_status.py"),
         str(tmp_path / "no_such_dir")],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert bad.returncode == 2


def test_run_report_serve_section_and_pre_telemetry_degrade(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import run_report
    finally:
        sys.path.pop(0)
    import io

    # r11-era dir: finalized serve manifest + heartbeats + an exemplar.
    live = tmp_path / "r11"
    os.makedirs(live)
    _write_serve_beats(live, 0, [_beat(40, 20.0, 2, 100.0)])
    with open(live / "manifest-serve-x.json", "w") as f:
        json.dump({
            "schema": 1, "kind": "serve", "outcome": "ok", "notes": {},
            "metrics": {"serve/p99_latency_ms": 21.0,
                        "serve/throughput_rps": 100.0,
                        "serve/slo_hit_frac": 0.999,
                        "serve/burn_rate": 0.1},
        }, f)
    out = io.StringIO()
    run_report.report_serve(str(live), out)
    text = out.getvalue()
    assert "outcome=ok" in text
    assert "p99 21.0 ms" in text and "SLO hit 99.90%" in text
    assert "serve replica 0" in text
    # PR-10-era dir: manifest only — graceful "(no serve telemetry" note.
    old = tmp_path / "r10"
    os.makedirs(old)
    with open(old / "manifest-serve-old.json", "w") as f:
        json.dump({
            "schema": 1, "kind": "serve", "outcome": "ok", "notes": {},
            "metrics": {"serve/p99_latency_ms": 30.0,
                        "serve/throughput_rps": 90.0},
        }, f)
    out = io.StringIO()
    run_report.report_serve(str(old), out)
    text = out.getvalue()
    assert "p99 30.0 ms" in text
    assert "(no serve telemetry" in text
    # And the main() auto-detection renders the section for a serve dir.
    rc = run_report.main([str(live)])
    assert rc == 0


# ----------------------------------------------------- sentinel surface


def test_sentinel_scores_slo_fixtures_both_directions(capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import regression_sentinel as sentinel
    finally:
        sys.path.pop(0)
    assert sentinel.main([os.path.join(FIXTURES, "slo_clean")]) == 0
    assert "ok      slo_hit_frac" in capsys.readouterr().out
    assert sentinel.main(
        ["--json", os.path.join(FIXTURES, "slo_regressed")]
    ) == 1
    report = json.loads(capsys.readouterr().out)
    flagged = {v["metric"] for v in report["verdicts"] if v["regressed"]}
    assert flagged == {"slo_hit_frac"}


def test_sentinel_skips_records_lacking_slo_hit_frac():
    """The attention_core_frac presence contract for slo_hit_frac:
    PR-10-era serve records (no SLO tracker) are skipped, never
    zero-filled, and a pre-telemetry candidate after r11 history is not
    scorable."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from regression_sentinel import judge_metric
    finally:
        sys.path.pop(0)
    from sav_tpu.obs.manifest import normalize_run_record

    def r11(slo, i):
        return normalize_run_record(
            {"outcome": "ok", "p99_latency_ms": 21.0,
             "serve_throughput": 400.0, "slo_hit_frac": slo},
            label=f"s{i}", index=i,
        )

    def r10(i):
        return normalize_run_record(
            {"outcome": "ok", "p99_latency_ms": 21.0,
             "serve_throughput": 400.0},
            label=f"old{i}", index=i,
        )

    history = [r10(0), r11(0.995, 1), r11(0.992, 2), r11(0.994, 3),
               r11(0.993, 4)]
    verdict = judge_metric(
        history, "slo_hit_frac", k=3.5, rel_floor=0.05, min_history=2
    )
    assert verdict is not None and not verdict.regressed
    assert judge_metric(
        [r10(i) for i in range(5)], "slo_hit_frac",
        k=3.5, rel_floor=0.05, min_history=2,
    ) is None
    assert judge_metric(
        history + [r10(5)], "slo_hit_frac",
        k=3.5, rel_floor=0.05, min_history=2,
    ) is None
    # Manifest shape: serve/slo_hit_frac surfaces as the metric name.
    rec = normalize_run_record({
        "schema": 1, "outcome": "ok",
        "metrics": {"serve/slo_hit_frac": 0.99},
    })
    assert rec.metrics["slo_hit_frac"] == 0.99


# ----------------------------------------------- structural no-sync proof


def test_batcher_drain_telemetry_is_structurally_sync_free():
    """The thread-guard twin of savlint SAV116, proved structurally: the
    batcher + telemetry import surface (everything the drain and the
    span/window/heartbeat paths execute) never imports jax — a device
    sync is unreachable from the drain by construction."""
    code = (
        "import sys\n"
        "import sav_tpu.serve.batcher, sav_tpu.serve.telemetry\n"
        "import sav_tpu.serve.latency\n"
        "assert 'jax' not in sys.modules, 'drain surface imported jax'\n"
        "assert 'numpy' not in sys.modules\n"
        "print('CLEAN')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    assert "CLEAN" in proc.stdout


# ------------------------------------------------------------ engine tier


def _tiny_config(**overrides):
    from sav_tpu.serve.engine import ServeConfig

    base = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        model_overrides={"num_layers": 1},
        buckets=[1, 2, 4],
        max_queue=128,
        deadline_ms=2000.0,
    )
    base.update(overrides)
    return ServeConfig(**base)


def _requests(n, image_size=32, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, (image_size, image_size, 3), dtype=np.uint8)
        for _ in range(n)
    ]


def test_engine_spans_complete_monotone_and_manifest_slo(tmp_path):
    """Real requests carry the full 8-stage walk, monotone; the ring
    holds them; the manifest carries slo_hit_frac end to end."""
    from sav_tpu.serve.engine import ServeEngine

    engine = ServeEngine(
        _tiny_config(buckets=[1, 4], deadline_ms=500.0,
                     log_dir=str(tmp_path), heartbeat_secs=0.2)
    )
    with engine:
        futures = [engine.submit(img) for img in _requests(8)]
        for f in futures:
            f.result(timeout=30.0)
        time.sleep(0.5)  # let at least one heartbeat fire
    engine.stop()
    telemetry = engine._telemetry
    records = telemetry.ring.records()
    assert len(records) == 8
    for rec in records:
        names = [s for s, _ in rec["stamps"]]
        assert names == list(STAGES), names
        times = [t for _, t in rec["stamps"]]
        assert times == sorted(times), "stamps not monotone"
        assert rec["dominant_stage"] in {
            name for name, _, _ in INTERVALS
        }
    assert telemetry.stats()["heartbeats"] >= 2  # cadence + final beat
    # Heartbeats landed in the fleet stream with the serve schema.
    beats = aggregate_serve(str(tmp_path))
    assert beats["replicas"]["0"]["requests"] == 8
    # slo_hit_frac flowed engine -> manifest -> normalize_run_record.
    from sav_tpu.obs.manifest import normalize_run_record

    manifests = [f for f in os.listdir(tmp_path) if f.startswith("manifest")]
    with open(os.path.join(tmp_path, manifests[0])) as f:
        data = json.load(f)
    assert data["metrics"]["serve/slo_hit_frac"] == 1.0
    assert data["metrics"]["serve/burn_rate"] == 0.0
    assert data["notes"]["serve_telemetry"]["slo"]["target"] == 0.99
    record = normalize_run_record(data, label="serve")
    assert record.metrics["slo_hit_frac"] == 1.0
    # The span ring's chrome export is on disk (replica-namespaced like
    # proc_<i>.jsonl) and traceview-readable.
    import glob as _glob

    ring_paths = _glob.glob(os.path.join(
        str(tmp_path), "serve_traces", "requests_proc*.trace.json.gz"
    ))
    assert len(ring_paths) == 1
    spans = traceview.request_spans(traceview.load_trace(ring_paths[0]))
    assert len(spans) == 8


def test_engine_live_stats_graceful_before_first_batch(tmp_path):
    """The bugfix satellite, engine half: live percentiles before the
    first completed batch are None (no IndexError), and a zero-request
    run finalizes an honest manifest WITHOUT slo_hit_frac (skip, not
    zero-fill)."""
    from sav_tpu.serve.engine import ServeEngine

    engine = ServeEngine(
        _tiny_config(buckets=[1], log_dir=str(tmp_path),
                     heartbeat_secs=0.1)
    )
    with engine:
        time.sleep(0.25)  # heartbeats fire on an idle engine
        stats = engine.stats()
        assert stats["live"]["p99_ms"] is None
        assert stats["live"]["requests"] == 0
        assert stats["slo"]["hit_frac"] is None
        assert stats["slo"]["burning"] is False
    summary = engine.stop()
    assert summary["requests"] == 0
    manifests = [f for f in os.listdir(tmp_path) if f.startswith("manifest")]
    with open(os.path.join(tmp_path, manifests[0])) as f:
        data = json.load(f)
    assert data["outcome"] == "ok"
    assert "serve/slo_hit_frac" not in data["metrics"]
    assert "serve/p99_latency_ms" not in data["metrics"]
    assert data["metrics"]["serve/requests"] == 0.0


def test_induced_spike_exemplar_names_stage_and_one_bounded_capture(
    tmp_path,
):
    """The acceptance e2e: an induced device-side latency spike yields
    >= 1 slow-request exemplar whose span timeline names the stage that
    ate the time (device, not queue), plus EXACTLY ONE bounded anomaly
    capture (armed/active/cooldown gating — PR-7's budget machinery)."""
    from sav_tpu.obs.autoprof import AutoProfiler
    from sav_tpu.serve.engine import ServeEngine

    starts, stops = [], []
    autoprof = AutoProfiler(
        str(tmp_path), trace_steps=2, max_captures=2,
        cooldown_steps=10_000,
        start_fn=lambda p: starts.append(p), stop_fn=lambda: stops.append(1),
        analyze=False,
    )
    seen = {"n": 0}

    def execute_hook(formed):
        seen["n"] += 1
        if seen["n"] == 30:
            time.sleep(0.8)  # one slow "device" batch

    engine = ServeEngine(
        _tiny_config(buckets=[1], deadline_ms=5000.0, log_dir=str(tmp_path),
                     heartbeat_secs=0.2, slow_sigma=20.0),
        autoprof=autoprof, execute_hook=execute_hook,
    )
    image = _requests(1)[0]
    with engine:
        for _ in range(40):
            engine.submit(image).result(timeout=30.0)
    engine.stop()
    # Exactly one bounded capture, serve-triggered, 2 batches wide.
    assert len(autoprof.captures) == 1
    capture = autoprof.captures[0]
    assert capture["trigger"] == "serve_p99_spike"
    assert capture["end_step"] - capture["start_step"] == 2
    assert len(starts) == 1 and len(stops) == 1
    # >= 1 exemplar, full span detail, device named as the eater. (CPU
    # jitter can flag an extra request; the INDUCED spike must be among
    # the exemplars regardless.)
    exemplars = find_exemplars(str(tmp_path))
    assert len(exemplars) >= 1
    by_rid = {e["rid"]: e for e in exemplars}
    assert 30 in by_rid, sorted(by_rid)
    slow = by_rid[30]
    assert slow["dominant_stage"] == "device"
    assert slow["stages_ms"]["device"] > 500.0
    assert slow["stages_ms"]["device"] > 10 * slow["stages_ms"]["queue"]
    assert slow["gate"]["window_n"] >= 16
    # serve_status renders the whole post-mortem from artifacts.
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_status.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    assert "Slow-request exemplars: " in proc.stdout
    assert "device dominated" in proc.stdout
    assert "Anomaly captures: 1" in proc.stdout
    assert "serve_p99_spike" in proc.stdout


def test_telemetry_overhead_within_two_percent(tmp_path):
    """The overhead acceptance, as what the layer does and not as how fast
    a shared CPU ran it (a throughput ratio of two floods on six busy
    workers measures the scheduler): with tracing + heartbeats + windows
    ON, the telemetry layer's work is bounded per BATCH and per heartbeat,
    never per request; the request path writes no file; and the layer's
    steady cost stays under 100us/request (~1% of a realistic 10ms serving
    latency) on the clock of the threads that do the work.

    - The layer times each of its sections with its ``perf`` clock. Counted
      here: exactly one section a completed batch and one a heartbeat,
      whatever the number of requests.
    - The same seam is handed ``time.thread_time``: the CPU seconds the
      layer's own threads spent inside a section. The steady cost is the
      MEDIAN section over the rows of a batch. A loaded host trips the
      latency gate, and the layer then does its bounded incident work
      inside a section (up to ``slow_exemplars`` = 8 bundle writes, one
      anomaly capture's profiler start and stop: 150 ms in a run of 96
      batches); that work is held to its counts, not billed to requests.
    - Bytes: the heartbeat stream gets one bounded line a beat; nothing
      else under the log dir grows with the requests while serving.
    """
    import gc
    import statistics

    from sav_tpu.serve.engine import ServeEngine

    n, floods = 256, 3
    log_dir = tmp_path / "on"
    engine = ServeEngine(_tiny_config(
        image_size=64, model_overrides={"num_layers": 4},
        buckets=[1, 8], max_queue=1024, deadline_ms=120000.0,
        telemetry=True, log_dir=str(log_dir), heartbeat_secs=0.5,
    ))
    reads = []

    def cpu_clock():
        reads.append(time.thread_time())
        return reads[-1]

    engine._telemetry._perf = cpu_clock
    images = _requests(n, image_size=64)
    engine.start()
    gc.collect()
    gc.disable()
    try:
        for _ in range(floods):
            futures = [engine.submit(img) for img in images]
            for f in futures:
                f.result(timeout=120.0)
        stats = engine.stats()["telemetry"]
        sections = [end - start for start, end in zip(reads[0::2], reads[1::2])]
        served_files = sorted(
            os.path.relpath(os.path.join(d, f), log_dir)
            for d, _, fs in os.walk(log_dir) for f in fs
        )
    finally:
        gc.enable()
        engine.stop()
    assert stats["requests"] == n * floods and stats["shed"] == 0.0
    assert stats["heartbeats"] >= 1
    # One timed section a batch, one a beat: a reader racing a beat sees
    # its clock reads before its count, never the other way round.
    assert 0 <= len(sections) - (stats["batches"] + stats["heartbeats"]) <= 1, (
        len(sections), stats
    )
    assert stats["batches"] < stats["requests"]  # eights formed under flood
    rows_per_batch = stats["requests"] / stats["batches"]
    per_request = statistics.median(sections) / rows_per_batch
    assert 0.0 < per_request <= 100e-6, (per_request, stats)
    # While serving, the log dir holds the heartbeat stream (+ the fleet
    # layer's rollups/alerts, time-driven like the beats), the run's
    # manifest, at most one anomaly capture and at most the bounded
    # slow-request exemplars: no file a request.
    assert stats["exemplars"] <= 8  # ServeConfig.slow_exemplars
    exemplar_files = [
        f for f in served_files if f.startswith("serve_traces" + os.sep)
    ]
    assert len(exemplar_files) == stats["exemplars"], exemplar_files
    others = [
        f for f in served_files
        if f not in exemplar_files
        and not f.startswith(("fleet" + os.sep, "autoprof" + os.sep, "manifest-"))
    ]
    assert others == [], others
    stream = log_dir / "fleet" / "proc_0.jsonl"
    lines = stream.read_text().splitlines()
    beats = [ln for ln in lines if json.loads(ln).get("kind") == "serve"]
    assert len(beats) >= stats["heartbeats"]  # + the closing beat(s)
    assert max(len(ln) for ln in lines) <= 4096, max(lines, key=len)


def test_serve_bench_zero_requests_honest_line(tmp_path):
    """The bugfix satellite, CLI half: serve_bench against an instantly
    drained (zero-request) engine emits an honest JSON line — requests
    0, null percentiles, no slo_hit_frac key — not a traceback."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    manifest = str(tmp_path / "manifest-zero.json")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "tools", "serve_bench.py"),
            "--model", "vit_ti_patch16", "--num-classes", "10",
            "--image-size", "32",
            "--model-overrides", '{"num_layers": 1}',
            "--buckets", "1", "--requests", "0",
            "--heartbeat-secs", "0.2",
            "--manifest", manifest,
        ],
        capture_output=True, text=True, timeout=420, cwd=ROOT, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["outcome"] == "ok"
    assert line["requests"] == 0
    assert line["p99_latency_ms"] is None
    assert line["serve_throughput"] == 0.0
    assert "slo_hit_frac" not in line
    assert line["telemetry"]["heartbeats"] >= 1
    with open(manifest) as f:
        data = json.load(f)
    assert data["outcome"] == "ok"
    assert "serve/slo_hit_frac" not in data["metrics"]


def test_begin_trace_adopts_propagated_fleet_id():
    """ISSUE 16 propagation: begin_trace ADOPTS a router-minted trace
    id (the wire header's ``r<pid>-<seq>``) instead of minting a
    replica-local one — the adoption is what joins the replica's spans
    to the router's in the offline fleet merge. Replica-local serving
    (no id to adopt) mints from the local counter exactly as before,
    and adoption does not consume local ids."""
    clock = FakeClock()
    telemetry = ServeTelemetry(clock=clock)
    local = telemetry.begin_trace(0.5)
    assert local.rid == 1
    adopted = telemetry.begin_trace(0.25, rid="r4242-7")
    assert adopted.rid == "r4242-7"
    assert adopted.deadline_s == 0.25
    assert adopted.stamps[0][0] == "submit"
    # The local counter did not advance for the adopted id.
    assert telemetry.begin_trace(0.5).rid == 2
