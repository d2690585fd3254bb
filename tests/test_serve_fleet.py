"""Serve fleet (ISSUE 15): replica pool, wait-aware router, chaos proof.

Four tiers:

- **Structural**: the router/pool import surface never pulls in jax
  (routing cannot sync a device value by construction — the batcher's
  proof, fleet-wide).
- **Fake-clock router units**: the projected-wait arithmetic pinned to
  the batcher's, min-wait routing, admission shed, transport failover
  + reroute, heartbeat-silence down/recovery, straggler drain/resume,
  and close semantics — no processes, no wall clocks.
- **Artifact units**: serve heartbeat streams -> ``aggregate_serve``
  dead-replica suspicion + ``router_views`` (the router consumes the
  SAME flag the offline tools render), the fleet sentinel metrics both
  directions, and the supervisor's serve-mode chain.
- **REAL process tier**: two replica processes behind the router via
  ``serve_bench --replicas`` — the straggler smoke (injected +latency
  on rank 1 -> load shifts to rank 0; fleet identity via the
  ``SAV_FLEET_PROC`` override, the two_process_smoke technique) and
  the CHAOS PROOF (SIGKILL a replica mid-flood: exact accounting —
  nothing silently lost — bounded fleet p99, warm supervisor restart
  with ``compiled_from_scratch == 0``, router fold-back).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from sav_tpu.serve.batcher import (  # noqa: E402
    DeadlineInfeasibleError,
    QueueFullError,
    ServeClosedError,
)
from sav_tpu.serve.router import (  # noqa: E402
    ReplicaShedError,
    ReplicaTransportError,
    Router,
    RouterShedError,
    projected_wait_s,
)

# --------------------------------------------------- structural no-jax


def test_router_fleet_surface_is_structurally_jax_free():
    """The router/pool import surface (everything admission, routing,
    spawning, and the wire client execute) never imports jax or numpy
    — the fleet-wide twin of the batcher's structural no-sync proof,
    and the supervisor-parent contract (the pool's parent must not be
    hangable by backend import)."""
    code = (
        "import sys\n"
        "import sav_tpu.serve.router, sav_tpu.serve.fleet\n"
        "import sav_tpu.serve.telemetry\n"
        "import sav_tpu.obs.rollup, sav_tpu.obs.alerts\n"
        "import tools.fleet_console\n"
        "assert 'jax' not in sys.modules, 'fleet surface imported jax'\n"
        "assert 'numpy' not in sys.modules\n"
        "print('CLEAN')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    assert "CLEAN" in proc.stdout


# ------------------------------------------------- projection math pins


def test_projected_wait_math_pinned():
    """The router's wait projection is the batcher's admission
    projection verbatim: ``inflight + ceil((queued + fresh) /
    max_batch)`` batches (the ``+ max_batch`` counting the request's
    own batch), each one estimated step."""
    # Idle replica: the request's own batch only.
    assert projected_wait_s(
        queued=0, inflight=0, fresh_outstanding=0, max_batch=8,
        est_step_s=0.05,
    ) == pytest.approx(0.05)
    # Batcher parity: 2 in flight + (20 queued + 4 fresh + 8)//8 = 5
    # queue batches -> 6 total (hand-computed against batcher.submit).
    assert projected_wait_s(
        queued=20, inflight=2, fresh_outstanding=4, max_batch=8,
        est_step_s=0.05,
    ) == pytest.approx(0.3)
    # An exactly-full queue ships (queued = max_batch -> 2 batches).
    assert projected_wait_s(
        queued=8, inflight=0, fresh_outstanding=0, max_batch=8,
        est_step_s=0.1,
    ) == pytest.approx(0.2)
    # Degenerate inputs clamp rather than explode.
    assert projected_wait_s(
        queued=0, inflight=0, fresh_outstanding=0, max_batch=8,
        est_step_s=-1.0,
    ) == 0.0
    assert projected_wait_s(
        queued=-5, inflight=-1, fresh_outstanding=0, max_batch=0,
        est_step_s=1.0,
    ) == pytest.approx(1.0)


# ------------------------------------------------ fake-clock router units


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += float(s)


class FakeTransport:
    """Per-rank scripted behavior: a result dict, an exception instance,
    or a callable. Records every send."""

    def __init__(self, behavior):
        self.behavior = dict(behavior)
        self.sends = []

    def send(self, rank, payload, meta, timeout_s):
        self.sends.append(rank)
        b = self.behavior[rank]
        if callable(b):
            b = b()
        if isinstance(b, BaseException):
            raise b
        return b


def _view(**kw):
    base = {
        "queued": 0, "inflight": 0, "est_step_s": 0.01, "p99_ms": 10.0,
        "last_beat_unix": 100.0, "beats": 5, "final": False,
        "suspect": False, "pid": 1000,
    }
    base.update(kw)
    return base


def make_router(views, transport, clock=None, wall=None, **kw):
    clock = clock or FakeClock()
    wall = wall or FakeClock(100.0)
    defaults = dict(
        views_fn=lambda: views,
        max_batch=2,
        default_step_s=0.01,
        default_deadline_s=1.0,
        refresh_secs=0.0,  # every admit refreshes (deterministic)
        workers=0,         # synchronous dispatch: admit blocks
        clock=clock,
        wall_clock=wall,
        sleep=clock.sleep,
    )
    defaults.update(kw)
    return Router(transport, **defaults), clock, wall


def test_route_picks_min_projected_wait_and_skips_unroutable():
    views = {
        0: _view(queued=8, est_step_s=0.1),   # 1 + (8+2)//2 = 6 -> 0.6
        1: _view(queued=0, est_step_s=0.1),   # 0 + 1 -> 0.1
        2: _view(queued=0, est_step_s=0.01),  # 0.01 — best
    }
    router, clock, _ = make_router(
        views, FakeTransport({0: {"ok": True}, 1: {"ok": True},
                              2: {"ok": True}})
    )
    assert router.route() == 2
    assert router.drain(2)
    assert router.route() == 1   # draining excluded, next-best wins
    views[1]["suspect"] = True
    router.refresh()
    assert router.route() == 0   # suspect down; only rank 0 remains
    router.close()


def test_admission_sheds_when_best_wait_blows_deadline():
    views = {
        0: _view(queued=20, inflight=2, est_step_s=0.2),
        1: _view(queued=40, inflight=1, est_step_s=0.2),
    }
    router, clock, _ = make_router(
        views, FakeTransport({0: {"ok": True}, 1: {"ok": True}})
    )
    # Best is rank 0: (2 + (20+2)//2) * 0.2 = 2.6s > the 1s default.
    with pytest.raises(DeadlineInfeasibleError):
        router.admit(b"x")
    assert router.stats()["shed_admit"] == 1
    # A deadline that fits is admitted and served.
    future = router.admit(b"x", deadline_s=10.0)
    assert future.result(timeout=0) == {"ok": True}
    router.close()
    assert router.summary()["shed"] == 1


def test_failover_marks_down_reroutes_and_recovers():
    views = {
        0: _view(est_step_s=0.001),
        1: _view(est_step_s=0.1),
    }
    transport = FakeTransport({
        0: ReplicaTransportError("connection reset"),
        1: {"ok": True, "pred": 7},
    })
    router, clock, wall = make_router(views, transport)
    # Rank 0 wins the projection, dies on send, gets marked down; the
    # request REROUTES to rank 1 and completes — never silently lost.
    future = router.admit(b"x")
    assert future.result(timeout=0)["pred"] == 7
    stats = router.stats()
    assert transport.sends == [0, 1]
    assert stats["transport_failures"] == 1
    assert stats["rerouted"] == 1
    assert stats["replicas"]["0"]["state"] == "down"
    assert "transport" in stats["replicas"]["0"]["down_reason"]
    assert stats["completed"] == 1
    # Recovery: a heartbeat NEWER than the down mark folds it back in.
    views[0]["last_beat_unix"] = wall() + 5.0
    transport.behavior[0] = {"ok": True, "pred": 0}
    router.refresh()
    assert router.stats()["replicas"]["0"]["state"] == "active"
    assert router.route() == 0
    router.close()


def test_all_replicas_down_sheds_at_deadline_never_hangs():
    views = {0: _view(), 1: _view()}
    transport = FakeTransport({
        0: ReplicaTransportError("dead"),
        1: ReplicaTransportError("dead"),
    })
    router, clock, _ = make_router(views, transport)
    future = router.admit(b"x", deadline_s=0.25)
    with pytest.raises(RouterShedError):
        future.result(timeout=0)
    stats = router.stats()
    assert stats["shed_deadline"] == 1
    assert stats["replicas"]["0"]["state"] == "down"
    assert stats["replicas"]["1"]["state"] == "down"
    # The fake clock advanced past the deadline via the poll sleeps —
    # the dispatch loop polls for recovery, it never busy-hangs.
    assert clock() >= 0.25
    router.close()


def test_straggler_loo_drains_and_resumes():
    views = {
        0: _view(p99_ms=10.0),
        1: _view(p99_ms=10.5),
        2: _view(p99_ms=200.0),  # the straggler
    }
    router, clock, _ = make_router(
        views,
        FakeTransport({0: {"ok": True}, 1: {"ok": True}, 2: {"ok": True}}),
    )
    router.refresh()
    stats = router.stats()["replicas"]
    assert stats["2"]["state"] == "draining"
    assert stats["0"]["state"] == stats["1"]["state"] == "active"
    assert router.route() in (0, 1)
    # Recovery: its window p99 returns to the pack -> resumed.
    views[2]["p99_ms"] = 11.0
    router.refresh()
    assert router.stats()["replicas"]["2"]["state"] == "active"
    router.close()


def test_never_drains_the_last_active_replica():
    views = {0: _view(p99_ms=500.0), 1: _view(p99_ms=10.0)}
    router, clock, _ = make_router(
        views, FakeTransport({0: {"ok": True}, 1: {"ok": True}})
    )
    views[1]["suspect"] = True  # rank 1 dies...
    router.refresh()
    stats = router.stats()["replicas"]
    # ...so rank 0, however slow its p99, must NOT also be drained.
    assert stats["1"]["state"] == "down"
    assert stats["0"]["state"] == "active"
    assert router.drain(0) is False
    assert router.route() == 0
    router.close()


def test_replica_shed_retries_until_deadline_then_sheds_honestly():
    views = {0: _view()}
    calls = {"n": 0}

    def shed_then_ok():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ReplicaShedError("replica queue full")
        return {"ok": True}

    transport = FakeTransport({0: shed_then_ok})
    router, clock, _ = make_router(views, transport)
    future = router.admit(b"x", deadline_s=5.0)
    assert future.result(timeout=0) == {"ok": True}
    assert calls["n"] == 3  # retried through the replica-side rejects
    router.close()


def test_close_fails_queued_and_stops_admission():
    views = {0: _view()}
    release = threading.Event()

    class BlockingTransport:
        def __init__(self):
            self.sent = 0

        def send(self, rank, payload, meta, timeout_s):
            self.sent += 1
            release.wait(10.0)
            return {"ok": True}

    transport = BlockingTransport()
    router = Router(
        transport, views_fn=lambda: views, workers=1, max_batch=2,
        refresh_secs=3600.0,
    )
    first = router.admit(b"a", deadline_s=30.0)
    deadline = time.monotonic() + 5.0
    while transport.sent == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert transport.sent == 1  # the single worker holds request A
    second = router.admit(b"b", deadline_s=30.0)
    # Release the held send shortly after close() starts draining, so
    # close's worker join returns promptly.
    threading.Timer(0.3, release.set).start()
    router.close()
    # B never shipped: failed loudly. A was already sent: completes.
    with pytest.raises(ServeClosedError):
        second.result(timeout=5.0)
    assert first.result(timeout=5.0) == {"ok": True}
    with pytest.raises(ServeClosedError):
        router.admit(b"c")


def test_router_rejects_past_max_inflight():
    views = {0: _view()}
    release = threading.Event()

    class HoldingTransport:
        def send(self, rank, payload, meta, timeout_s):
            release.wait(10.0)
            return {"ok": True}

    router = Router(
        HoldingTransport(), views_fn=lambda: views, workers=1,
        max_inflight=2, refresh_secs=3600.0,
    )
    router.admit(b"a", deadline_s=30.0)
    router.admit(b"b", deadline_s=30.0)
    with pytest.raises(QueueFullError):
        router.admit(b"c", deadline_s=30.0)
    assert router.stats()["rejected"] == 1
    release.set()
    router.close()


# ------------------------------------ distributed tracing units (ISSUE 16)


class StampingTransport:
    """A stamp-aware fake transport (the TcpTransport seam): stamps
    connect/sent at scripted clock instants, so the router's
    transport_send / replica_wait intervals have pinned durations."""

    supports_stamps = True

    def __init__(self, clock, *, connect_s=0.002, exchange_s=0.010,
                 result=None):
        self.clock = clock
        self.connect_s = connect_s
        self.exchange_s = exchange_s
        self.result = result if result is not None else {"ok": True}
        self.metas = []

    def send(self, rank, payload, meta, timeout_s, stamp_fn=None):
        self.metas.append(dict(meta))
        if stamp_fn is not None:
            stamp_fn("connect")
        self.clock.sleep(self.connect_s)
        if stamp_fn is not None:
            stamp_fn("sent")
        self.clock.sleep(self.exchange_s)
        r = self.result
        if callable(r):
            r = r()
        if isinstance(r, BaseException):
            raise r
        return r


def test_router_traces_full_walk_ring_and_export(tmp_path):
    """Tentpole part 1: every admitted request is one end-to-end trace.
    The router mints a globally unique ``r<pid>-<seq>`` id, propagates
    it on the wire header (``meta["trace"]``), stamps the full
    lifecycle through the transport's connect/sent seam, and exports
    the span ring as a chrome trace at close — in the router interval
    vocabulary, with rank/outcome join keys for the offline merge."""
    from sav_tpu.obs.traceview import _span_bounds, load_trace

    views = {0: _view()}
    clock = FakeClock()
    transport = StampingTransport(clock)
    router, _, _ = make_router(
        views, transport, clock=clock, log_dir=str(tmp_path)
    )
    first = router.admit(b"x", deadline_s=1.0)
    second = router.admit(b"y", deadline_s=1.0)
    assert first.result(timeout=0) == {"ok": True}
    assert second.result(timeout=0) == {"ok": True}
    rids = [m["trace"] for m in transport.metas]
    assert rids == [f"r{os.getpid()}-0", f"r{os.getpid()}-1"]
    assert len(set(rids)) == 2  # globally unique: pid + private seq
    summ = router.summary()
    assert summ["traces"] == {"ring": 2, "appended": 2}
    assert summ["router_overhead_ms"] >= 0.0
    router.close()
    path = os.path.join(
        str(tmp_path), "serve_traces", "requests_router.trace.json.gz"
    )
    assert os.path.exists(path)
    bounds = _span_bounds(load_trace(path))
    assert set(bounds) == set(rids)
    at = bounds[rids[0]]["at"]
    for name in ("admission", "router_queue", "route", "transport_send",
                 "replica_wait", "deliver"):
        assert name in at, f"missing {name} interval in the export"
    # transport_send spans the socket's connect->sent instants (2 ms);
    # the exchange itself is the opaque replica_wait (10 ms) the
    # offline merge decomposes.
    send = at["transport_send"]
    assert send[1] - send[0] == pytest.approx(2000.0)
    wait = at["replica_wait"]
    assert wait[1] - wait[0] == pytest.approx(10000.0)
    assert bounds[rids[0]]["args"]["rank"] == 0
    assert bounds[rids[0]]["args"]["outcome"] == "completed"


def test_reroute_records_attempt_sub_spans_and_candidate_waits():
    """A rerouted request's trace carries one sub-span per attempt
    (failed rank first, serving rank second) plus the candidate wait
    table the routing decision saw — the Tail-at-Scale WHY."""
    views = {0: _view(est_step_s=0.001), 1: _view(est_step_s=0.1)}
    transport = FakeTransport({
        0: ReplicaTransportError("connection reset"),
        1: {"ok": True},
    })
    router, clock, _ = make_router(views, transport)
    assert router.admit(b"x").result(timeout=0) == {"ok": True}
    rec = router._ring.records()[0]
    assert rec["outcome"] == "completed"
    assert [a["rank"] for a in rec["attempts"]] == [0, 1]
    assert [a["outcome"] for a in rec["attempts"]] == [
        "transport_error", "ok",
    ]
    assert set(rec["candidate_waits_ms"]) == {0, 1}
    assert rec["candidate_waits_ms"][0] < rec["candidate_waits_ms"][1]
    assert rec["dominant_stage"] is not None
    router.close()


def test_shed_trace_ends_with_honest_terminal_stamp():
    """A shed request's trace ends with the honest ``shed`` stamp —
    never a fake ``completed`` — and folds into the ring with its real
    outcome (the merged fleet view must show where load was refused)."""
    views = {0: _view()}
    transport = FakeTransport({0: ReplicaTransportError("dead")})
    router, clock, _ = make_router(views, transport)
    future = router.admit(b"x", deadline_s=0.25)
    with pytest.raises(RouterShedError):
        future.result(timeout=0)
    rec = router._ring.records()[0]
    assert rec["outcome"] == "shed"
    assert rec["stamps"][-1][0] == "shed"
    assert rec["hit"] is False
    assert rec["rank"] is None
    router.close()


def test_live_and_summary_agree_mid_run():
    """The ISSUE-16 bugfix pin: the throughput/percentiles serve_status
    reads MID-RUN (``live()``) are the same numbers ``summary()``
    reports at close — previously throughput existed only in the
    close-time summary, so a mid-run status could not be compared to
    the post-run record."""
    views = {0: _view()}
    clock = FakeClock()
    transport = StampingTransport(clock, connect_s=0.0, exchange_s=0.01)
    router, _, _ = make_router(views, transport, clock=clock)
    for _ in range(5):
        router.admit(b"x", deadline_s=5.0).result(timeout=0)
        clock.sleep(0.09)  # spaced load; last gap is BEFORE the reads
    clock.t = router._last_complete_t  # read at the last completion
    live = router.live()
    summ = router.summary()
    assert live["completed"] == summ["completed"] == 5
    assert live["throughput_rps"] == summ["throughput_rps"]
    assert live["w"] == summ["window"]
    # The windowed view divides by the EFFECTIVE span (run younger than
    # the window), so the windowed rate agrees with the span rate too.
    assert live["w"]["throughput_rps"] == summ["throughput_rps"]
    assert live["w"]["p99_ms"] == summ["latency_ms"]["p99"] == 10.0
    # Stage shares: the whole windowed latency sat in replica_wait.
    assert live["w"]["stage_shares"] == {"replica_wait": 1.0}
    assert live["router_overhead_ms"] == summ["router_overhead_ms"]
    router.close()


def test_router_heartbeats_on_fleet_substrate(tmp_path):
    """The router is a first-class fleet citizen: ``kind=router`` beats
    on the PR-7 heartbeat substrate (``fleet/router.jsonl``), carrying
    the live windowed view + the trace-overhead meter; close() appends
    a final beat so the last written state is the closing state."""
    from sav_tpu.obs.fleet import read_router_beats

    views = {0: _view()}
    clock = FakeClock()
    transport = StampingTransport(clock, connect_s=0.0, exchange_s=0.01)
    router, _, _ = make_router(
        views, transport, clock=clock, log_dir=str(tmp_path)
    )
    router.admit(b"x", deadline_s=5.0).result(timeout=0)
    assert router.router_beat() is True
    beats = read_router_beats(str(tmp_path))
    assert len(beats) == 1
    beat = beats[0]
    assert beat["kind"] == "router"
    assert beat["completed"] == 1
    assert beat["w"]["requests"] == 1
    assert beat["w"]["p99_ms"] == 10.0
    assert "router_overhead_ms" in beat
    assert os.path.exists(
        os.path.join(str(tmp_path), "fleet", "router.jsonl")
    )
    router.close()
    final = read_router_beats(str(tmp_path))
    assert len(final) == 2  # close() appended the closing beat
    assert final[-1]["completed"] == 1


def test_plain_transport_degrades_to_contiguous_stamps():
    """A transport WITHOUT the stamp seam still produces a contiguous
    walk: connect/sent collapse to the pre-send instant, so the whole
    exchange lands in replica_wait and no interval is missing."""
    from sav_tpu.serve.telemetry import ROUTER_INTERVALS, intervals

    views = {0: _view()}
    router, clock, _ = make_router(
        views, FakeTransport({0: {"ok": True}})
    )
    router.admit(b"x").result(timeout=0)
    rec = router._ring.records()[0]
    stages = intervals(rec["stamps"], ROUTER_INTERVALS)
    assert set(stages) >= {"transport_send", "replica_wait", "deliver"}
    assert stages["transport_send"] == 0.0
    router.close()


def test_tcp_transport_declares_the_stamp_seam():
    """The production TcpTransport is the stamp-aware side of the seam:
    the capability flag the router keys on, and the send/_exchange
    signatures that accept the stamp callback."""
    import inspect

    from sav_tpu.serve.fleet import TcpTransport

    assert TcpTransport.supports_stamps is True
    assert "stamp_fn" in inspect.signature(TcpTransport.send).parameters
    assert "stamp_fn" in inspect.signature(
        TcpTransport._exchange
    ).parameters


# --------------------------------- heartbeat artifacts -> suspicion/views


def _write_serve_stream(log_dir, proc, times, *, pid=1000, final=False,
                        step_s=0.01, queued=0, inflight=0, p99=12.0,
                        capacity=None, rps=50.0):
    os.makedirs(os.path.join(log_dir, "fleet"), exist_ok=True)
    path = os.path.join(log_dir, "fleet", f"proc_{proc}.jsonl")
    with open(path, "a") as f:
        for t in times:
            record = {
                "schema": 1, "kind": "serve", "proc": proc, "procs": 2,
                "t": t, "pid": pid, "queued": queued, "inflight": inflight,
                "requests": 10, "shed": 0,
                "w": {"p99_ms": p99, "step_s_avg": step_s,
                      "queue_depth_last": queued, "throughput_rps": rps},
                "slo": {"hit_frac": 1.0, "burn_rate": 0.0,
                        "burning": False},
            }
            if capacity is not None:
                record["capacity_rps"] = capacity
            f.write(json.dumps(record) + "\n")
        if final:
            f.write(json.dumps({
                "schema": 1, "kind": "final", "proc": proc,
                "outcome": "ok", "t": times[-1] + 0.1,
            }) + "\n")


def test_aggregate_serve_flags_silent_replica_and_router_consumes_it(
    tmp_path,
):
    """Satellite: a SIGKILLed serve replica no longer just vanishes —
    aggregate_serve lists it under ``suspects`` (silent > 3x the fleet
    median beat interval, no final record), its view carries
    ``suspect: true``, and ``router_views`` hands the router the SAME
    flag (one detection body, obs.fleet.silence_suspects)."""
    from sav_tpu.serve.telemetry import aggregate_serve, router_views

    log_dir = str(tmp_path)
    _write_serve_stream(log_dir, 0, [float(t) for t in range(11)])
    _write_serve_stream(
        log_dir, 1, [0.0, 1.0, 2.0, 3.0], pid=2000, queued=3, inflight=1,
        step_s=0.2, p99=80.0,
    )
    summary = aggregate_serve(log_dir, now=10.0)
    assert [s["proc"] for s in summary["suspects"]] == [1]
    assert summary["suspects"][0]["silent_s"] == pytest.approx(7.0)
    assert summary["replicas"]["1"]["suspect"] is True
    assert summary["replicas"]["0"]["suspect"] is False
    assert summary["fleet"]["suspects"] == [1]
    views = router_views(log_dir, now=10.0)
    assert views[1]["suspect"] is True
    assert views[1]["queued"] == 3
    assert views[1]["inflight"] == 1
    assert views[1]["est_step_s"] == pytest.approx(0.2)
    assert views[1]["p99_ms"] == pytest.approx(80.0)
    assert views[1]["pid"] == 2000
    assert views[0]["suspect"] is False
    # Offline default ('now' = newest beat anywhere): same flag.
    assert [s["proc"] for s in aggregate_serve(log_dir)["suspects"]] == [1]


def test_final_record_is_a_close_not_a_death(tmp_path):
    from sav_tpu.serve.telemetry import aggregate_serve

    log_dir = str(tmp_path)
    _write_serve_stream(log_dir, 0, [float(t) for t in range(11)])
    _write_serve_stream(log_dir, 1, [0.0, 1.0, 2.0], final=True)
    summary = aggregate_serve(log_dir, now=10.0)
    assert summary["suspects"] == []
    assert summary["replicas"]["1"]["final"] is True


def test_stale_final_does_not_close_a_restarted_replica(tmp_path):
    """Regression: the heartbeat streams are append-only across
    restarts, so a ``final`` from a PREVIOUS generation (a graceful
    stop before a pool restart over the same log dir) followed by
    fresh beats must NOT mark the replica closed — that would make the
    router permanently down every replica of a reused log dir and shed
    100% of the second run. Only a final at least as new as the newest
    beat counts."""
    from sav_tpu.serve.telemetry import aggregate_serve, router_views

    log_dir = str(tmp_path)
    _write_serve_stream(log_dir, 0, [float(t) for t in range(11)])
    # Generation 1: beats, then an orderly final. Generation 2 (pool
    # restart, new pid): fresh beats APPENDED after the final.
    _write_serve_stream(log_dir, 1, [0.0, 1.0, 2.0], pid=2000, final=True)
    _write_serve_stream(
        log_dir, 1, [8.0, 9.0, 10.0], pid=3000,
    )
    summary = aggregate_serve(log_dir, now=10.0)
    assert summary["replicas"]["1"]["final"] is False
    assert summary["replicas"]["1"]["pid"] == 3000
    assert summary["suspects"] == []
    views = router_views(log_dir, now=10.0)
    assert views[1]["final"] is False
    assert views[1]["suspect"] is False


def test_aggregate_serve_folds_capacity_and_headroom(tmp_path):
    """ISSUE 19: with capacity stamps on the beats and a rolled
    throughput series, aggregate_serve folds fleet capacity, the
    Theil–Sen load projection, and headroom_frac; without stamps the
    fold stays silent (skip-not-zero-fill)."""
    from sav_tpu.obs.rollup import Roller
    from sav_tpu.serve.telemetry import aggregate_serve

    log_dir = str(tmp_path)
    # 40 beats at 1 Hz per replica, throughput climbing 1 rps/s each.
    for proc in (0, 1):
        _write_serve_stream(
            log_dir, proc,
            [float(t) for t in range(40)],
            capacity=400.0, rps=100.0,
        )
    roller = Roller(log_dir)
    roller.roll_once()
    roller.flush()
    summary = aggregate_serve(log_dir, now=40.0)
    fleet = summary["fleet"]
    assert summary["replicas"]["0"]["capacity_rps"] == 400.0
    assert fleet["capacity_rps"] == 800.0
    # Flat 100 rps per replica -> flat 200 rps projection, headroom
    # (800 - 200) / 800 = 0.75.
    assert fleet["projected_rps"] == pytest.approx(200.0, rel=0.01)
    assert fleet["headroom_frac"] == pytest.approx(0.75, abs=0.01)
    assert fleet["load_rps"] == pytest.approx(200.0, rel=0.01)
    # Un-rolled dir: the beat-timeline fallback still projects.
    bare = str(tmp_path / "bare")
    for proc in (0, 1):
        _write_serve_stream(
            bare, proc, [float(t) for t in range(11)], capacity=150.0,
        )
    fleet2 = aggregate_serve(bare, now=11.0)["fleet"]
    assert fleet2["capacity_rps"] == 300.0
    assert isinstance(fleet2["headroom_frac"], float)
    # No capacity stamps anywhere -> NO capacity/headroom keys.
    plain = str(tmp_path / "plain")
    _write_serve_stream(plain, 0, [0.0, 1.0, 2.0])
    fleet3 = aggregate_serve(plain, now=3.0)["fleet"]
    assert "capacity_rps" not in fleet3
    assert "headroom_frac" not in fleet3


def test_read_heartbeats_tail_bound_reads_recent_lines_only(tmp_path):
    """The router's live view is tail-bounded: a refresh parses only
    each stream's trailing bytes (constant cost however long the run),
    dropping the partial first line of the mid-file seek — while the
    offline default still reads everything."""
    from sav_tpu.obs.fleet import read_heartbeats

    log_dir = str(tmp_path)
    _write_serve_stream(
        log_dir, 0, [float(t) for t in range(200)], p99=12.0
    )
    full = read_heartbeats(log_dir)[0]
    assert len(full) == 200
    tail = read_heartbeats(log_dir, tail_bytes=4096)[0]
    assert 0 < len(tail) < 200
    # The tail is the NEWEST suffix, whole lines only.
    assert [r["t"] for r in tail] == [r["t"] for r in full[-len(tail):]]
    # And the live router view built on it still carries the headline.
    from sav_tpu.serve.telemetry import router_views

    views = router_views(log_dir, now=199.0, tail_bytes=4096)
    assert views[0]["p99_ms"] == pytest.approx(12.0)
    assert views[0]["suspect"] is False


# ------------------------------------------------ fleet sentinel metrics


FIXDIR = os.path.join(os.path.dirname(__file__), "sentinel_fixtures")


def _sentinel(argv):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import regression_sentinel
    finally:
        sys.path.pop(0)
    return regression_sentinel.main(argv)


def test_sentinel_scores_fleet_fixtures_both_directions(capsys):
    assert _sentinel([os.path.join(FIXDIR, "fleet_clean")]) == 0
    capsys.readouterr()
    assert _sentinel([os.path.join(FIXDIR, "fleet_regressed")]) == 1
    out = capsys.readouterr().out
    assert "fleet_p99_latency_ms" in out
    assert "fleet_throughput" in out


def test_sentinel_scores_headroom_both_directions(capsys):
    """fleet_headroom_frac (ISSUE 19): the capacity/headroom fold is
    sentinel-gated in BOTH directions — a hovering ~0.40 history stays
    clean, and a collapse to 0.10 flags even though latency and
    throughput stay flat (saturation risk surfaces before the tail
    moves; that is the whole point of the fold)."""
    assert _sentinel([os.path.join(FIXDIR, "headroom_clean")]) == 0
    out = capsys.readouterr().out
    assert "fleet_headroom_frac" in out
    assert _sentinel([os.path.join(FIXDIR, "headroom_regressed")]) == 1
    out = capsys.readouterr().out
    assert "REGRESS fleet_headroom_frac" in out
    assert "REGRESS fleet_p99_latency_ms" not in out  # tail stayed flat
    # Skip-not-zero-fill: records without capacity stamps (pre-19
    # fleet lines, manifests without the fold) never contribute.
    from sav_tpu.obs.manifest import MANIFEST_SCHEMA, normalize_run_record

    rec = normalize_run_record(
        {"outcome": "ok", "fleet_p99_latency_ms": 35.0,
         "fleet_throughput": 700.0, "fleet_headroom_frac": 0.4},
        label="new", index=0,
    )
    assert rec.metrics["fleet_headroom_frac"] == 0.4
    mrec = normalize_run_record(
        {"schema": MANIFEST_SCHEMA, "outcome": "ok",
         "kind": "serve_fleet", "metrics": {"fleet/headroom_frac": 0.37}},
        label="m", index=1,
    )
    assert mrec.metrics["fleet_headroom_frac"] == 0.37
    old = normalize_run_record(
        {"outcome": "ok", "fleet_p99_latency_ms": 35.0,
         "fleet_throughput": 700.0},
        label="old", index=2,
    )
    assert "fleet_headroom_frac" not in old.metrics


def test_sentinel_scores_router_overhead_both_directions(capsys):
    """router_overhead_ms (ISSUE 16): the router's self-accounted
    tracing cost is sentinel-gated — flat history stays ok, a jump past
    the 0.05 ms absolute floor flags (observability taxing the routing
    hot path IS a regression), while the surrounding fleet metrics stay
    clean in both fixture directions."""
    assert _sentinel([os.path.join(FIXDIR, "router_clean")]) == 0
    out = capsys.readouterr().out
    assert "router_overhead_ms" in out
    assert _sentinel([os.path.join(FIXDIR, "router_regressed")]) == 1
    out = capsys.readouterr().out
    assert "REGRESS router_overhead_ms" in out
    assert "REGRESS fleet" not in out  # only the overhead series moved


def test_router_overhead_skip_not_zero_fill():
    """Records lacking router_overhead_ms (pre-16 fleet records, plain
    serve records, training records) are SKIPPED, never zero-filled —
    the attention_core_frac presence contract — and the metric reads
    from both record shapes (bench line + serve_fleet manifest)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from regression_sentinel import judge_metric
    finally:
        sys.path.pop(0)
    from sav_tpu.obs.manifest import MANIFEST_SCHEMA, normalize_run_record

    traced = {
        "outcome": "ok", "fleet_p99_latency_ms": 35.0,
        "fleet_throughput": 700.0, "router_overhead_ms": 0.02,
    }
    rec = normalize_run_record(traced, label="traced", index=0)
    assert rec.metrics["router_overhead_ms"] == 0.02
    manifest = {
        "schema": MANIFEST_SCHEMA, "outcome": "ok", "kind": "serve_fleet",
        "metrics": {"fleet/router_overhead_ms": 0.03},
    }
    mrec = normalize_run_record(manifest, label="m", index=1)
    assert mrec.metrics["router_overhead_ms"] == 0.03
    # A pre-16 fleet record lacks it entirely — never zero-filled.
    untraced = normalize_run_record(
        {"outcome": "ok", "fleet_p99_latency_ms": 35.0,
         "fleet_throughput": 700.0},
        label="old", index=2,
    )
    assert "router_overhead_ms" not in untraced.metrics
    # Newest record lacking it -> unscorable, not re-judged stale.
    records = [
        normalize_run_record(dict(traced), label=f"t{i}", index=i)
        for i in range(3)
    ] + [untraced]
    assert judge_metric(
        records, "router_overhead_ms", k=3.5, rel_floor=0.05,
        min_history=2,
    ) is None


def test_fleet_metrics_skip_not_zero_fill():
    """A training record after fleet records must not zero-fill the
    fleet metrics (unscorable, the attention_core_frac contract), and
    fleet metrics read from both record shapes (line + manifest)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from regression_sentinel import judge_metric
    finally:
        sys.path.pop(0)
    from sav_tpu.obs.manifest import MANIFEST_SCHEMA, normalize_run_record

    fleet_line = {
        "outcome": "ok", "fleet_p99_latency_ms": 35.0,
        "fleet_throughput": 700.0,
    }
    rec = normalize_run_record(fleet_line, label="fleet", index=0)
    assert rec.metrics["fleet_p99_latency_ms"] == 35.0
    assert rec.metrics["fleet_throughput"] == 700.0
    assert "fleet" in rec.detail and "p99" in rec.detail
    manifest = {
        "schema": MANIFEST_SCHEMA, "outcome": "ok", "kind": "serve_fleet",
        "metrics": {"fleet/p99_latency_ms": 30.0,
                    "fleet/throughput_rps": 650.0},
    }
    mrec = normalize_run_record(manifest, label="m", index=1)
    assert mrec.metrics["fleet_p99_latency_ms"] == 30.0
    assert mrec.metrics["fleet_throughput"] == 650.0
    # Training record lacks them entirely — never zero-filled.
    train = normalize_run_record(
        {"outcome": "ok", "value": 100.0, "unit": "img/s"},
        label="train", index=2,
    )
    assert "fleet_p99_latency_ms" not in train.metrics
    # Newest record lacking the metric -> unscorable, not re-judged.
    records = [
        normalize_run_record(dict(fleet_line), label=f"f{i}", index=i)
        for i in range(3)
    ] + [train]
    assert judge_metric(
        records, "fleet_p99_latency_ms", k=3.5, rel_floor=0.05,
        min_history=2,
    ) is None


# ------------------------------------------- supervisor serve-mode chain


def test_supervisor_serve_mode_stop_and_restart(tmp_path):
    """Serve-mode chain semantics: a SIGKILLed serve child restarts
    (the PR-9 contract), and a REQUESTED stop ends the chain with
    outcome ok and zero lost wall — a terminating server is a
    completed serve, not a crash."""
    from sav_tpu.train.supervisor import Supervisor

    log_dir = str(tmp_path / "chain")
    os.makedirs(log_dir)
    manifest_src = str(tmp_path / "manifest-serve-r0.json")
    child = [sys.executable, "-c",
             "import time, json, sys; "
             f"open({manifest_src!r}, 'w').write(json.dumps("
             "{'schema': 1, 'outcome': 'running'})); "
             "time.sleep(600)"]
    sup = Supervisor(
        child, log_dir=log_dir, checkpoint_dir=None, max_restarts=2,
        backoff_base_s=0.05, backoff_max_s=0.1, capture=True,
        serve=True, manifest_src=manifest_src,
    )
    rc_holder = {}
    thread = threading.Thread(target=lambda: rc_holder.update(
        rc=sup.run()))
    thread.start()
    # Attempt 1: SIGKILL -> restart (serve chains restart on kill).
    deadline = time.monotonic() + 30.0
    while sup.child is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert sup.child is not None
    first_pid = sup.child.pid
    # Let the child register its manifest before the kill, so the
    # preservation path has something to copy aside.
    while not os.path.exists(manifest_src) and time.monotonic() < deadline:
        time.sleep(0.02)
    assert os.path.exists(manifest_src)
    os.kill(first_pid, 9)
    while (
        (sup.child is None or sup.child.pid == first_pid)
        and time.monotonic() < deadline
    ):
        time.sleep(0.02)
    assert sup.child.pid != first_pid, "supervisor did not restart"
    # Requested stop: chain ends ok even though the child dies by
    # signal.
    sup.request_stop()
    sup.child.terminate()
    thread.join(30.0)
    assert not thread.is_alive()
    assert rc_holder["rc"] == 0
    with open(os.path.join(log_dir, "supervisor.json")) as f:
        doc = json.load(f)
    assert doc["outcome"] == "ok"
    assert doc["notes"]["stop_requested"] is True
    attempts = doc["notes"]["chain"]["attempts"]
    assert len(attempts) == 2
    assert attempts[0]["restart_reason"] == "killed:SIGKILL"
    assert attempts[1]["stopped"] is True
    assert attempts[1]["restart_reason"] is None
    assert attempts[1]["lost_s"] == 0.0
    # The per-attempt manifest preservation followed manifest_src.
    assert os.path.exists(
        os.path.join(log_dir, "attempts", "attempt_001.manifest.json")
    )


def test_replica_flag_vocabulary_consistent_across_tools():
    """serve_fleet.add_model_args and serve_bench's parser declare the
    engine/model flag set independently, with replica_argv forwarding
    between them — pin the vocabulary so it cannot drift: every flag
    replica_argv emits is declared by add_model_args, is spelled in
    serve_bench's parser too (so `serve_bench --replicas` can set it),
    and round-trips through the replica-mode parser with its values
    intact."""
    import argparse

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import serve_fleet
    finally:
        sys.path.pop(0)

    fleet_parser = argparse.ArgumentParser()
    serve_fleet.add_model_args(fleet_parser)
    fleet_flags = {
        a.option_strings[0]
        for a in fleet_parser._actions
        if a.option_strings
    }
    forwarded = {
        "--model", "--num-classes", "--image-size", "--backend",
        "--max-batch", "--max-queue", "--deadline-ms",
        "--heartbeat-secs", "--slo-target", "--model-overrides",
        "--buckets", "--checkpoint", "--layout-preset",
        "--compilation-cache-dir", "--attn-tune-cache",
        "--probe-every",
    }
    missing = forwarded - fleet_flags
    assert not missing, (
        f"replica_argv forwards {sorted(missing)} but add_model_args "
        "does not declare them"
    )
    with open(os.path.join(ROOT, "tools", "serve_bench.py")) as f:
        bench_src = f.read()
    for flag in sorted(forwarded):
        assert f'"{flag}"' in bench_src, (
            f"serve_bench's parser lost {flag} — fleet mode could no "
            "longer forward it to the replicas"
        )
    # Round trip: replica_argv's emitted argv parses cleanly back
    # through the replica-mode parser with the same values.
    ns = argparse.Namespace(
        model="vit_ti_patch16", num_classes=10, image_size=32,
        backend="auto", max_batch=2, max_queue=64, deadline_ms=500.0,
        heartbeat_secs=0.5, slo_target=0.99,
        model_overrides='{"num_layers": 1}', buckets="1,2",
        checkpoint=None, layout_preset=None,
        compilation_cache_dir="/tmp/cache", attn_tune_cache=None,
        probe_every=5.0,
    )
    argv = serve_fleet.replica_argv(ns, 1, "/tmp/logs")[2:]
    fleet_parser.add_argument("--replica-rank", type=int)
    fleet_parser.add_argument("--log-dir")
    fleet_parser.add_argument("--manifest")
    parsed = fleet_parser.parse_args(argv)
    assert parsed.model == "vit_ti_patch16"
    assert parsed.replica_rank == 1
    assert parsed.max_batch == 2
    assert parsed.deadline_ms == 500.0
    assert parsed.buckets == "1,2"
    assert parsed.model_overrides == '{"num_layers": 1}'
    assert parsed.compilation_cache_dir == "/tmp/cache"
    assert parsed.probe_every == 5.0
    assert parsed.manifest.endswith("manifest-serve-r1.json")


def test_pool_wait_ready_fails_fast_on_dead_chain(tmp_path):
    """A replica that crashes on startup exhausts its restart budget in
    seconds; wait_ready must surface that immediately (RuntimeError
    naming the rank) instead of sitting out the full startup timeout."""
    from sav_tpu.serve.fleet import ReplicaPool

    pool = ReplicaPool(
        replicas=1,
        child_argv_fn=lambda r: [
            sys.executable, "-c", "import sys; sys.exit(2)"
        ],
        log_dir=str(tmp_path),
        max_restarts=1,
        backoff_base_s=0.05,
    )
    pool.start()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="supervisor chain ended"):
        pool.wait_ready(timeout_s=120.0)
    assert time.monotonic() - t0 < 30.0  # failed fast, not at timeout
    pool.stop()


def test_pool_endpoint_registry_roundtrip(tmp_path):
    from sav_tpu.serve.fleet import (
        pid_alive,
        read_endpoint,
        read_endpoints,
        write_endpoint,
    )

    log_dir = str(tmp_path)
    path = write_endpoint(
        log_dir, 1, host="127.0.0.1", port=4242,
        startup={"compiled_from_scratch": 0}, platform="cpu",
    )
    assert path and os.path.exists(path)
    doc = read_endpoint(log_dir, 1)
    assert doc["port"] == 4242
    assert doc["pid"] == os.getpid()
    assert doc["startup"]["compiled_from_scratch"] == 0
    assert read_endpoints(log_dir) == {1: doc}
    assert pid_alive(os.getpid())
    reaped = subprocess.Popen([sys.executable, "-c", "pass"])
    reaped.wait()
    assert not pid_alive(reaped.pid)  # fully reaped child
    assert not pid_alive(None)
    assert read_endpoint(log_dir, 7) is None


# --------------------------------------------- REAL two-process fleet tier


BENCH_TIMEOUT = 420


@pytest.fixture(scope="module")
def fleet_cache_dir(tmp_path_factory):
    """One persistent compile cache shared by every fleet bench in this
    module: the first replica startup compiles the (tiny, identical)
    executables from scratch, everything after warm-starts — which is
    also what makes the chaos test's ``compiled_from_scratch == 0``
    restart proof representative."""
    return str(tmp_path_factory.mktemp("fleet_xla_cache"))


def _run_fleet_bench(tmp_path, tag, cache_dir, extra, lockwatch=False,
                     env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if env_extra:
        env.update(env_extra)
    if lockwatch:
        # Arm the runtime lock sanitizer (ISSUE 18): the router/
        # transport/telemetry locks are tracked and the observed
        # acquisition graph lands in log_dir/lockwatch.json. Only the
        # chaos proof runs armed — tracked locks add ~40µs to the
        # per-request dispatch path, which would pollute the smoke
        # test's strict ≤100µs tracing-overhead measurement.
        env["SAV_LOCKWATCH"] = "1"
    log_dir = str(tmp_path / tag)
    manifest = os.path.join(log_dir, f"manifest-fleet-{tag}.json")
    argv = [
        sys.executable, os.path.join(ROOT, "tools", "serve_bench.py"),
        "--model", "vit_ti_patch16", "--num-classes", "10",
        "--image-size", "32", "--model-overrides", '{"num_layers": 1}',
        # Bucket-1 ladder: every request ships immediately (no trickle
        # wait for a bucket to fill), so fleet latency measures routing
        # + service, not the batcher's deadline slack — the dynamic-
        # batching policy itself is test_serve.py's beat.
        "--buckets", "1", "--max-batch", "1",
        "--heartbeat-secs", "0.3", "--router-refresh-secs", "0.2",
        "--compilation-cache-dir", cache_dir,
        "--manifest", manifest, "--log-dir", log_dir,
        "--replica-startup-timeout", "240",
    ] + extra
    proc = subprocess.run(
        argv, capture_output=True, text=True, timeout=BENCH_TIMEOUT,
        cwd=ROOT, env=env,
    )
    assert proc.returncode == 0, (
        f"serve_bench --replicas failed:\n{proc.stdout[-3000:]}\n"
        f"{proc.stderr[-3000:]}"
    )
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    return line, log_dir, manifest


@pytest.mark.usefixtures("fleet_cache_dir")
def test_fleet_smoke_two_replicas_router_shifts_load(
    tmp_path, fleet_cache_dir, monkeypatch
):
    """The tier-1 fleet serve smoke: TWO real replica processes (fleet
    identity via the SAV_FLEET_PROC override the pool sets — the
    two_process_smoke technique), one router, +1.0s injected per-batch
    latency on rank 1. The router must shift load toward rank 0 while
    rank 1 still serves (draining/straggler pressure, not exclusion),
    and the accounting must balance exactly.

    What separates the two replicas is the injected second, a sleep of
    the test's own, and every threshold below sits between it and what a
    healthy replica does with a margin a loaded CI host does not eat: the
    alert rule fires past 900 ms (rank 1 cannot answer under 1,000; rank
    0 serves its ~40 one-row batches in a few ms each, and its queue's
    tail has read 707 ms beside five other xdist workers: at 700 the
    rule fired on it twice in the builders' tier-1 runs of PRs 39-40),
    the deadline (30 s) admits the whole flood whichever way the first
    requests split, and the router's own meter is held to a bound that
    one file write or socket call on the stamp path would break and a
    preempted thread does not.

    ISSUE 19 rides the same run: an operator latency rule (via the
    SAV_ALERT_RULES env seam) must produce EXACTLY ONE firing->resolved
    episode on the straggler, the capacity/headroom fold must land in
    the bench line and manifest metrics, and the ops console must
    render from rollups alone (zero raw-stream re-parses)."""
    rules_path = str(tmp_path / "alert_rules.json")
    with open(rules_path, "w") as f:
        json.dump({"rules": [{
            # The +1.0 s injected batch delay puts rank 1's windowed
            # p99 over 1,000 ms; rank 0's stays in the tens of ms on an
            # idle host and under 900 on a loaded one.
            "name": "slow-replica-p99", "severity": "warn",
            "when": [
                {"metric": "w.p99_ms", "op": ">", "value": 900.0},
            ],
            # Fire on the first hot beat; resolve only via the orderly
            # close (the injected delay never recovers in-run), so the
            # run yields exactly one episode.
            "for_s": 0, "resolve_s": 3600,
        }]}, f)
    line, log_dir, manifest = _run_fleet_bench(
        tmp_path, "smoke", fleet_cache_dir,
        [
            "--replicas", "2", "--requests", "48", "--rate", "0",
            "--deadline-ms", "30000", "--inject-delay", "1:1.0",
            "--probe-requests", "0", "--drain-timeout", "120",
        ],
        env_extra={"SAV_ALERT_RULES": rules_path},
    )
    assert line["outcome"] == "ok"
    assert line["replicas"] == 2
    acct = line["accounting"]
    assert acct["offered"] == 48
    assert acct["lost"] == 0, f"requests silently lost: {acct}"
    assert acct["errors"] == 0
    assert acct["completed"] + acct["shed"] + acct["closed"] == 48
    assert acct["completed"] >= 40  # the fleet actually served
    routed = {
        rank: v["routed"]
        for rank, v in line["router"]["replicas"].items()
    }
    assert routed["0"] > routed["1"], (
        f"router did not shift load away from the slow replica: {routed}"
    )
    assert line["router"]["replicas"]["0"]["completed"] > 0
    # Both replicas heartbeated into the shared dir under their own
    # identity; serve_status renders the fleet offline.
    status = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_status.py"),
         "--json", log_dir],
        capture_output=True, text=True, timeout=120,
    )
    assert status.returncode == 0, status.stderr
    summary = json.loads(status.stdout)
    assert set(summary["replicas"]) == {"0", "1"}
    assert summary["router"]["completed"] == acct["completed"]
    # The fleet line is sentinel-scoreable.
    from sav_tpu.obs.manifest import normalize_run_record

    rec = normalize_run_record(line, label="smoke", index=0)
    assert rec.ok
    assert rec.metrics["fleet_p99_latency_ms"] > 0
    assert rec.metrics["fleet_throughput"] > 0
    with open(manifest) as f:
        mdoc = json.load(f)
    assert mdoc["kind"] == "serve_fleet"
    assert mdoc["outcome"] == "ok"
    assert mdoc["metrics"]["fleet/p99_latency_ms"] == (
        line["fleet_p99_latency_ms"]
    )
    # ---------------- distributed tracing acceptance (ISSUE 16) ----------
    # ONE merged chrome trace for the whole fleet run: the router's span
    # ring + both replicas' exports joined offline into contiguous
    # router->replica->router chains.
    from sav_tpu.obs.traceview import fleet_request_spans, load_trace

    traces = line["serve_traces"]
    assert traces["router"] and os.path.exists(traces["router"])
    assert len(traces["replicas"]) == 2
    assert traces["merged"] and traces["merged"].endswith(
        "fleet.trace.json.gz"
    )
    # The per-request stamp cost stays bounded. The router's own meter
    # is a wall clock around two short sections a request (admit, the
    # terminal fold), lock waits included: its 100 us/request contract is
    # an idle host's number. Held here to 2 ms, which host work on the
    # stamp path (a file, a socket, a sort over the ring) breaks at once
    # and scheduling noise over ~100 sections does not; and to what the
    # router decides: one attempt and a bounded walk of stamps a request.
    assert line["router_overhead_ms"] is not None
    assert 0.0 <= line["router_overhead_ms"] <= 2.0, (
        f"router tracing overhead {line['router_overhead_ms']}ms/request"
    )
    router_events = [
        e for e in load_trace(traces["router"]) if e.get("ph") == "X"
    ]
    assert router_events, "the router exported no spans"
    assert len(router_events) <= 16 * acct["offered"], len(router_events)
    merged = fleet_request_spans(log_dir)
    assert merged["requests"], "the merge joined no requests"
    full = {
        rid: e for rid, e in merged["requests"].items()
        if not e["router_only"]
    }
    assert full, "no request merged across processes (all router-only)"
    for rid, e in merged["requests"].items():
        stages = e["stages"]
        assert stages, f"{rid} merged with an empty chain"
        # Contiguous: each stage starts where the previous ended.
        for prev, cur in zip(stages, stages[1:]):
            assert cur[1] == pytest.approx(
                prev[1] + prev[2], abs=2e-3
            ), f"{rid} chain is not contiguous at {cur[0]}"
    # Per-request stage sums match the client-observed latency within
    # the stamped skew bound (plus the sub-ms pre-admit sliver and
    # rounding).
    for rid, e in full.items():
        client_ms = e["deadline_ms"] + e["overrun_ms"]
        skew = e["skew_ms"] or 0.0
        # (100 ms: a thread preempted between its last stamp and the
        # latency read is not a hole in the chain; the injected second,
        # were a stage to drop it, is.)
        assert abs(client_ms - e["total_ms"]) <= skew + 100.0, (
            f"{rid}: merged chain {e['total_ms']}ms vs client "
            f"{client_ms}ms exceeds the {skew}ms skew bound"
        )
    # Every replica the merge used states its clock skew honestly.
    assert merged["replicas"], "no per-replica clock offset estimated"
    for proc, est in merged["replicas"].items():
        assert est["pairs"] >= 1
        assert est["skew_ms"] >= 0.0
    # The induced straggler (rank 1, +1.0 s per batch) shows up in the
    # fleet exemplars with the blame on the REPLICA side of the chain —
    # the cross-process attribution this PR exists for.
    exemplar_paths = sorted(
        p for p in os.listdir(os.path.join(log_dir, "serve_traces"))
        if p.startswith("slow_fleet_")
    )
    assert exemplar_paths, "no fleet exemplars written"
    exemplars = []
    for name in exemplar_paths:
        with open(os.path.join(log_dir, "serve_traces", name)) as f:
            exemplars.append(json.load(f))
    assert line["serve_traces"]["fleet_exemplars"] == len(exemplars)
    straggled = [
        e for e in exemplars
        if not e["router_only"]
        and e["dominant_stage"] in ("replica_queue", "device")
    ]
    assert straggled, (
        "no exemplar blamed the straggler's replica-side stages: "
        f"{[(e['rid'], e['dominant_stage']) for e in exemplars]}"
    )
    # The merged artifact is ONE trace every existing consumer reads.
    events = load_trace(traces["merged"])
    fleet_names = {
        e["args"]["name"] for e in events if e.get("ph") == "M"
    }
    assert fleet_names == {"Fleet Requests"}
    # The router heartbeated as a fleet citizen (kind=router stream),
    # and serve_status surfaced both the beats and the live window.
    from sav_tpu.obs.fleet import read_router_beats

    beats = read_router_beats(log_dir)
    assert beats, "router wrote no kind=router heartbeats"
    assert beats[-1]["completed"] == acct["completed"]
    assert summary["router_beats"] >= 1
    assert summary["router_live"]["completed"] == acct["completed"]
    # The manifest points at every trace artifact (run_report's hook).
    assert mdoc["notes"]["serve_traces"]["merged"] == traces["merged"]
    # -------------- fleet metrics pipeline acceptance (ISSUE 19) ---------
    # The straggler rule produced EXACTLY ONE firing->resolved episode,
    # fired by the slow replica, resolved at its orderly close.
    from sav_tpu.obs.alerts import episodes, read_alerts

    events = [
        e for e in read_alerts(log_dir) if e["rule"] == "slow-replica-p99"
    ]
    assert [(e["event"], e["proc"]) for e in events] == [
        ("firing", 1), ("resolved", 1),
    ], f"expected one firing->resolved episode on rank 1: {events}"
    eps = episodes(read_alerts(log_dir))["slow-replica-p99"]
    assert eps["fired"] == 1 and eps["resolved"] == 1
    assert eps["active"] is False
    # The episode is on the bench line and in the manifest notes.
    assert line["alerts"]["slow-replica-p99"]["fired"] == 1
    assert mdoc["notes"]["alerts"]["slow-replica-p99"]["fired"] == 1
    # Capacity/headroom fold: replicas stamped measured capacity_rps,
    # the fold summed it and projected load over the rollup series.
    assert line["fleet_capacity_rps"] > 0
    assert isinstance(line["fleet_headroom_frac"], float)
    assert -1.0 <= line["fleet_headroom_frac"] <= 1.0
    assert mdoc["metrics"]["fleet/headroom_frac"] == (
        line["fleet_headroom_frac"]
    )
    assert mdoc["notes"]["fleet"]["capacity_rps"] == (
        line["fleet_capacity_rps"]
    )
    assert rec.metrics["fleet_headroom_frac"] == (
        line["fleet_headroom_frac"]
    )
    # The router's heartbeat thread rolled IN-RUN (cursor + tiers exist
    # independent of the bench's post-run flush).
    assert os.path.exists(
        os.path.join(log_dir, "fleet", "rollup.cursor.json")
    )
    assert os.path.exists(
        os.path.join(log_dir, "fleet", "rollup_10.jsonl")
    )
    # The ops console renders from rollups + alerts ALONE: with the raw
    # heartbeat readers booby-trapped, gather() still renders and only
    # the instrumented rollup reader moved.
    import io

    from sav_tpu.obs import fleet as fleet_mod
    from sav_tpu.obs import rollup as rollup_mod

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import fleet_console
    finally:
        sys.path.pop(0)

    def _boom(*a, **k):
        raise AssertionError(
            "console re-parsed a raw heartbeat stream"
        )

    monkeypatch.setattr(fleet_mod, "read_heartbeats", _boom)
    monkeypatch.setattr(fleet_mod, "read_router_beats", _boom)
    reads_before = rollup_mod.READS["read_rollup"]
    snapshot = fleet_console.gather(log_dir)
    rendered = io.StringIO()
    fleet_console.render(snapshot, rendered)
    assert rollup_mod.READS["read_rollup"] > reads_before
    assert snapshot["capacity_rps"] > 0
    assert isinstance(snapshot["headroom_frac"], float)
    assert set(snapshot["replicas"]) == {"0", "1"}
    assert snapshot["alerts"]["slow-replica-p99"]["fired"] == 1
    text = rendered.getvalue()
    assert "capacity" in text and "headroom" in text
    # And the user-facing CLI agrees (fresh process, --once --json).
    console = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_console.py"),
         "--once", "--json", log_dir],
        capture_output=True, text=True, timeout=60,
    )
    assert console.returncode == 0, console.stderr
    doc = json.loads(console.stdout)
    assert doc["headroom_frac"] == snapshot["headroom_frac"]
    assert subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_console.py"),
         "--once", str(tmp_path / "not_a_run")],
        capture_output=True, text=True, timeout=60,
    ).returncode == 2


def test_fleet_chaos_sigkill_mid_flood_bounded_p99_warm_restart(
    tmp_path, fleet_cache_dir
):
    """THE chaos proof (acceptance criterion): two real replicas under
    flood, SIGKILL rank 1 mid-load. Every accepted request completes or
    is honestly shed (none silently lost), fleet p99 stays bounded (no
    cliff — the tail never absorbs the restart outage, and it stays
    within a generous multiple of the single-replica baseline), the
    supervisor restarts the victim WARM (``compiled_from_scratch ==
    0``), and the router folds it back in (the post-restart probe burst
    lands requests on it)."""
    # Single-replica baseline first (also covers --replicas 1 and warms
    # the shared cache for the chaos replicas).
    base_line, _, _ = _run_fleet_bench(
        tmp_path, "baseline", fleet_cache_dir,
        [
            "--replicas", "1", "--requests", "24", "--rate", "0",
            "--deadline-ms", "4000", "--probe-requests", "0",
            "--drain-timeout", "120",
        ],
    )
    assert base_line["accounting"]["lost"] == 0
    p99_base = base_line["fleet_p99_latency_ms"]
    assert p99_base and p99_base > 0

    line, log_dir, manifest = _run_fleet_bench(
        tmp_path, "chaos", fleet_cache_dir,
        [
            "--replicas", "2", "--requests", "48", "--rate", "0",
            "--deadline-ms", "6000",
            "--chaos-kill-rank", "1", "--chaos-kill-at-frac", "0.4",
            "--chaos-recovery-timeout", "180",
            "--probe-requests", "12",
            "--max-restarts", "2", "--restart-backoff", "0.3",
            "--drain-timeout", "180",
        ],
        lockwatch=True,
    )
    assert line["outcome"] == "ok"
    # 1. Exact accounting: nothing silently lost, no errors. A stuck
    # future would surface as a drain TimeoutError -> errors, so
    # lost == 0 AND errors == 0 is the none-silently-dropped proof
    # even when overload sheds part of the load honestly.
    acct = line["accounting"]
    assert acct["offered"] == 48
    assert acct["lost"] == 0, f"requests silently lost: {acct}"
    assert acct["errors"] == 0
    assert acct["completed"] + acct["shed"] + acct["closed"] == 48
    assert acct["completed"] >= 32  # the fleet kept serving through it
    # 2. The kill really happened mid-load and the supervisor absorbed
    # it: exactly one restart, reason SIGKILL, warm from the cache.
    chaos = line["chaos"]
    assert chaos["killed_pid"]
    assert line["restarts"] == 1
    assert chaos["outage_s"] > 0.5  # a real multi-second process death
    restart = chaos["restart_startup"]
    assert restart["compiled_from_scratch"] == 0, (
        f"victim restart was not warm: {restart}"
    )
    assert line["startup_warm"]["1"] == 0
    # 3. Bounded fleet p99 — no cliff. A cliff is the tail absorbing
    # the restart: requests parked on the dead replica completing only
    # after the multi-second outage, i.e. p99 far PAST the deadline
    # contract. Bounded = within the admitted-request contract
    # (deadline + bounded completion slack) AND within a generous
    # multiple of the single-replica flood baseline (CPU CI noise
    # allowed for; the cliff alternative is orders of magnitude).
    p99 = line["fleet_p99_latency_ms"]
    assert p99 and p99 > 0
    assert p99 <= 6000.0 + 2000.0, (
        f"fleet p99 {p99}ms blew past the deadline contract — the tail "
        "absorbed the restart outage"
    )
    assert p99 <= max(25.0 * p99_base, 6000.0), (
        f"fleet p99 {p99}ms cliffed vs single-replica baseline "
        f"{p99_base}ms"
    )
    # 4. Rerouting did the absorbing: the victim's in-flight work came
    # back as transport failures and was rerouted, not dropped.
    assert line["transport_failures"] >= 1
    assert line["rerouted"] >= 1
    # 5. The router folded the restarted victim back in: the probe
    # burst landed requests on it.
    probe = line["probe_routed"]
    assert probe["1"] > 0, f"router never resumed routing to victim: {probe}"
    # 6. One sentinel-scoreable fleet line + finalized manifest.
    from sav_tpu.obs.manifest import normalize_run_record

    rec = normalize_run_record(line, label="chaos", index=0)
    assert rec.ok
    assert rec.metrics["fleet_p99_latency_ms"] == p99
    with open(manifest) as f:
        mdoc = json.load(f)
    assert mdoc["outcome"] == "ok"
    assert mdoc["metrics"]["fleet/restarts"] == 1.0
    assert mdoc["notes"]["fleet"]["chaos"]["rank"] == 1
    # The supervisor chain for the victim recorded the kill.
    with open(os.path.join(
        log_dir, "replicas", "rank_1", "supervisor.json"
    )) as f:
        chain = json.load(f)
    attempts = chain["notes"]["chain"]["attempts"]
    assert attempts[0]["restart_reason"] == "killed:SIGKILL"
    assert chain["outcome"] == "ok"  # requested stop at bench teardown
    # 7. Lock sanitizer acceptance (ISSUE 18): the whole chaos run —
    # flood, kill, reroute storm, warm restart, probe burst — executed
    # under lockwatch and observed ZERO lock-order inversions, and
    # every observed acquisition is one the static SAV122 graph
    # predicts (exit 0 from lockgraph's --observed cross-check; a
    # cycle or a linter blind spot would exit 1).
    lockwatch_path = os.path.join(log_dir, "lockwatch.json")
    with open(lockwatch_path) as f:
        lw = json.load(f)
    assert lw["cycles"] == [], (
        f"lock-order inversion observed during chaos: {lw['cycles']}"
    )
    assert "Router._lock" in lw["locks"]  # sanitizer was actually armed
    crosscheck = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "lockgraph.py"),
         "--observed", lockwatch_path],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert crosscheck.returncode == 0, (
        f"observed lock graph inconsistent with static SAV122 graph:\n"
        f"{crosscheck.stdout}\n{crosscheck.stderr}"
    )
