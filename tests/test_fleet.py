"""Fleet telemetry (ISSUE 7): heartbeats, skew, stragglers, dead hosts.

Tier-1 acceptance criteria live here and in tests/test_two_process.py:
per-process heartbeat streams appear at the trainer's log boundary with
zero extra device syncs and <1% step overhead (the goodput-ledger guard,
same pattern as the recorder's), the aggregator's leave-one-out
median+MAD ranking names an injected-delay process as the straggler, a
silent process raises dead-host suspicion, the merged fleet manifest is
written atomically by the fit, and the report tools degrade gracefully
on runs with no ``fleet/`` dir.
"""

import importlib.util
import io
import json
import os
import sys

import numpy as np
import pytest

from sav_tpu.obs.fleet import (
    HeartbeatWriter,
    aggregate_fleet,
    fleet_dir,
    heartbeat_path,
    read_heartbeats,
    write_fleet_manifest,
)
from sav_tpu.obs.goodput import GoodputLedger
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


# ------------------------------------------------------------ writer unit


class FakeClock:
    def __init__(self, t0=1000.0):
        self.t = t0

    def __call__(self):
        return self.t


def _ledger_with(step_s=1.0, input_wait_s=0.0):
    ledger = GoodputLedger()
    ledger.account("step", step_s)
    ledger.account("input_wait", input_wait_s)
    ledger.steps = 4
    return ledger


def test_heartbeat_writer_appends_schema_records(tmp_path):
    clock = FakeClock()
    writer = HeartbeatWriter(
        str(tmp_path), process_index=3, process_count=8, clock=clock
    )
    writer.beat(
        10,
        ledger=_ledger_with(step_s=2.0, input_wait_s=0.5),
        metrics={"loss": 1.25, "images_per_sec": 100.0, "retraces": 0.0},
    )
    clock.t += 5.0
    writer.beat(20, ledger=_ledger_with(), incident="incidents/step_20")
    writer.fleet_event("watchdog_soft", silent_s=12.0)
    writer.close(outcome="ok")
    path = heartbeat_path(str(tmp_path), 3)
    assert path.endswith(os.path.join("fleet", "proc_3.jsonl"))
    records = [json.loads(ln) for ln in open(path) if ln.strip()]
    kinds = [r["kind"] for r in records]
    assert kinds == ["hb", "hb", "event", "final"]
    # schema_version (ISSUE 19): every record kind carries the writer's
    # generation stamp next to the frozen line-shape schema.
    from sav_tpu.obs.fleet import FLEET_SCHEMA_VERSION

    assert FLEET_SCHEMA_VERSION == 2
    assert [r["schema_version"] for r in records] == [2, 2, 2, 2]
    assert all(r["schema"] == 1 for r in records)
    hb = records[0]
    assert hb["proc"] == 3 and hb["procs"] == 8 and hb["step"] == 10
    assert hb["b"]["step"] == 2.0 and hb["b"]["input_wait"] == 0.5
    assert hb["loss"] == 1.25 and hb["retraces"] == 0
    assert records[1]["incident"] == "incidents/step_20"
    assert records[2]["event"] == "watchdog_soft"
    assert records[3]["outcome"] == "ok"
    stats = writer.stats()
    assert stats["beats"] == 2.0 and stats["events"] == 1.0
    # Idempotent close; post-close beats are dropped, not errors.
    writer.close()
    writer.beat(30, ledger=_ledger_with())
    assert len(read_heartbeats(str(tmp_path))[3]) == 4


def test_read_heartbeats_skips_torn_tail(tmp_path):
    writer = HeartbeatWriter(str(tmp_path), process_index=0)
    writer.beat(1, ledger=_ledger_with())
    writer.close()
    with open(heartbeat_path(str(tmp_path), 0), "a") as f:
        f.write('{"kind": "hb", "step"')  # a killed writer's torn line
    records = read_heartbeats(str(tmp_path))[0]
    assert [r["kind"] for r in records] == ["hb", "final"]


def test_readers_tolerate_future_schema_versions(tmp_path):
    """Forward compat (ISSUE 19): a NEWER writer's records — higher
    schema_version, unknown keys, even unknown kinds — pass through the
    readers untouched; old readers filter on ``kind`` and must never
    crash or drop on a version bump."""
    path = heartbeat_path(str(tmp_path), 0)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(json.dumps({
            "schema": 1, "schema_version": 99, "kind": "hb", "proc": 0,
            "t": 1.0, "step": 5, "from_the_future": {"x": 1},
        }) + "\n")
        f.write(json.dumps({
            "schema": 1, "schema_version": 99, "kind": "hologram",
            "proc": 0, "t": 2.0,
        }) + "\n")
        f.write(json.dumps({
            "schema": 1, "schema_version": 99, "kind": "final",
            "proc": 0, "t": 3.0, "outcome": "ok",
        }) + "\n")
    records = read_heartbeats(str(tmp_path))[0]
    assert [r["kind"] for r in records] == ["hb", "hologram", "final"]
    assert records[0]["from_the_future"] == {"x": 1}
    # Aggregation sees through the unknown records too.
    summary = aggregate_fleet(str(tmp_path))
    proc = summary["processes"]["0"]
    assert proc["outcome"] == "ok"


# ------------------------------------------------------- aggregation unit


def _write_stream(tmp_path, proc, entries, final=None):
    path = heartbeat_path(str(tmp_path), proc)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        for e in entries:
            record = {"schema": 1, "kind": "hb", "proc": proc}
            record.update(e)
            f.write(json.dumps(record) + "\n")
        if final is not None:
            f.write(json.dumps({
                "schema": 1, "kind": "final", "proc": proc,
                "outcome": final,
                "t": entries[-1]["t"] if entries else 0.0,
            }) + "\n")


def _stream(proc, *, t0=0.0, per_step=1.0, steps=10, stall_frac=0.0):
    """Synthetic heartbeat trail: one beat per step, constant rate, the
    host-stall buckets accruing ``stall_frac`` of each interval."""
    entries = []
    wall = 0.0
    b = {"step": 0.0, "input_wait": 0.0, "h2d": 0.0, "stall": 0.0,
         "eval": 0.0, "checkpoint": 0.0, "compile": 0.0}
    for i in range(1, steps + 1):
        wall += per_step
        b = dict(b)
        b["input_wait"] += per_step * stall_frac
        b["step"] += per_step * (1 - stall_frac)
        entries.append({
            "step": i, "t": round(t0 + wall, 3), "b": b,
            "wall_s": round(wall, 3), "anomalies": 0,
        })
    return entries


def test_straggler_ranking_names_injected_slow_process(tmp_path):
    """Four processes, one 3x slower: the leave-one-out median+MAD
    ranking flags exactly it, by raw step time."""
    for proc in range(3):
        _write_stream(tmp_path, proc, _stream(proc, per_step=1.0),
                      final="ok")
    _write_stream(tmp_path, 3, _stream(3, per_step=3.0), final="ok")
    summary = aggregate_fleet(str(tmp_path))
    ranking = summary["straggler"]["ranking"]
    assert summary["straggler"]["straggler"] == 3
    assert ranking[0]["proc"] == 3 and ranking[0]["flagged"]
    assert not any(e["flagged"] for e in ranking[1:])
    assert summary["processes"]["3"]["median_step_s"] == pytest.approx(3.0)


def test_straggler_by_host_stall_share_in_lockstep_fleet(tmp_path):
    """The collective-run signature (docs/fleet.md): every process shows
    the SAME wall per-step (lockstep), but the straggler's time sits in
    input_wait while the victims' sits in step — attribution must name
    the process that stalled BEFORE the all-reduce, not report a
    symmetric slowdown."""
    for proc in range(3):
        _write_stream(
            tmp_path, proc,
            _stream(proc, per_step=2.0, stall_frac=0.02), final="ok",
        )
    _write_stream(
        tmp_path, 3, _stream(3, per_step=2.0, stall_frac=0.7), final="ok"
    )
    summary = aggregate_fleet(str(tmp_path))
    assert summary["straggler"]["straggler"] == 3
    top = summary["straggler"]["ranking"][0]
    assert top["proc"] == 3
    assert top["host_stall"]["flagged"]
    # Step time alone could not have separated them (lockstep).
    assert not top["step_time"]["flagged"]


def test_missing_heartbeat_raises_dead_host_suspicion(tmp_path):
    """'Process 1 stopped heartbeating at step 4' — the MULTICHIP/bench
    post-mortem this layer exists for."""
    _write_stream(tmp_path, 0, _stream(0, per_step=1.0, steps=12),
                  final="ok")
    _write_stream(tmp_path, 1, _stream(1, per_step=1.0, steps=4))
    summary = aggregate_fleet(str(tmp_path))
    suspects = summary["suspects"]
    assert [s["proc"] for s in suspects] == [1]
    assert suspects[0]["last_step"] == 4
    assert suspects[0]["silent_s"] == pytest.approx(8.0)
    assert summary["step_skew"]["skew"] == 8
    assert summary["step_skew"]["laggard"] == 1
    # A process WITH a final record is finished, not dead.
    assert "0" in summary["processes"]
    assert summary["processes"]["0"]["final"]


def test_aggregate_empty_dir_and_single_process(tmp_path):
    assert aggregate_fleet(str(tmp_path))["processes"] == {}
    _write_stream(tmp_path, 0, _stream(0), final="ok")
    summary = aggregate_fleet(str(tmp_path))
    # One process: nobody to compare against — no straggler, no crash.
    assert summary["straggler"]["straggler"] is None
    assert summary["suspects"] == []


def test_fleet_manifest_written_atomically(tmp_path):
    _write_stream(tmp_path, 0, _stream(0), final="ok")
    summary = aggregate_fleet(str(tmp_path))
    path = write_fleet_manifest(str(tmp_path), summary)
    assert path == os.path.join(fleet_dir(str(tmp_path)), "fleet.json")
    with open(path) as f:
        assert json.load(f)["schema"] == 1
    assert not [
        n for n in os.listdir(fleet_dir(str(tmp_path))) if ".tmp." in n
    ]


# ---------------------------------------------------------------- fit e2e


def _fit_config(tmp_path, **overrides):
    base = dict(
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
        fleet=True,
        seed=0,
        model_overrides={"num_layers": 1, "embed_dim": 32, "num_heads": 2},
    )
    base.update(overrides)
    return TrainConfig(**base)


def _batches(n):
    rng = np.random.default_rng(0)
    for _ in range(n):
        yield {
            "images": rng.standard_normal((8, 32, 32, 3)).astype(np.float32),
            "labels": rng.integers(0, 10, (8,), dtype=np.int32),
        }


def test_fit_heartbeats_on_log_boundary_with_overhead_guard(
    tmp_path, devices
):
    """The tier-1 sync/overhead contract: heartbeats appear at every log
    boundary of a real fit, the merged fleet manifest lands next to
    them, and the whole fleet path costs <1% of step time on the
    training thread (goodput-ledger guard — the recorder's pattern;
    SAV112 is the static half of the same contract)."""
    config = _fit_config(tmp_path, log_every_steps=4)
    trainer = Trainer(config)
    from sav_tpu.obs.manifest import RunManifest

    manifest = RunManifest(
        os.path.join(str(tmp_path), "manifest.json"), kind="train"
    )
    manifest.begin()
    state, history = trainer.fit(
        _batches(16), num_steps=16, manifest=manifest
    )
    records = read_heartbeats(str(tmp_path))[0]
    beats = [r for r in records if r["kind"] == "hb"]
    # 16 steps at log_every=4 -> 4 log boundaries, then one final record.
    assert [b["step"] for b in beats] == [4, 8, 12, 16]
    assert records[-1]["kind"] == "final"
    assert records[-1]["outcome"] == "ok"
    for b in beats:
        assert b["b"]["step"] > 0  # ledger buckets ride every beat
        assert "loss" in b
    # Merged fleet manifest written by the fit itself (process 0).
    with open(os.path.join(fleet_dir(str(tmp_path)), "fleet.json")) as f:
        merged = json.load(f)
    assert merged["processes"]["0"]["heartbeats"] == 4
    assert merged["processes"]["0"]["final"]
    # ... and cross-linked from the run manifest.
    doc = RunManifest.load(manifest.path)
    assert doc["notes"]["fleet"]["processes"]["0"]["last_step"] == 16
    # Overhead: the fleet path (writes included) stays under 1% of step.
    gauges = trainer.last_goodput["gauges"]
    assert gauges["fleet/beats"] == 4.0
    step_s = trainer.last_goodput["buckets_s"]["step"]
    assert step_s > 0
    assert gauges["fleet/write_s"] < 0.01 * step_s, (
        f"fleet heartbeat overhead {gauges['fleet/write_s']:.6f}s is not "
        f"<1% of step time {step_s:.6f}s"
    )


def test_fit_without_fleet_or_log_dir_writes_nothing(tmp_path, devices):
    config = _fit_config(tmp_path, fleet=False)
    Trainer(config).fit(_batches(4), num_steps=4)
    assert not os.path.isdir(fleet_dir(str(tmp_path)))


def test_identity_override_gates_shared_writers(
    tmp_path, devices, monkeypatch
):
    """SAV_FLEET_PROC != 0 makes a worker a NON-writer for the shared
    files (goodput.json, the merged fleet manifest) while still
    heartbeating into its own stream — independent workers sharing a
    log dir must not clobber each other (docs/fleet.md)."""
    monkeypatch.setenv("SAV_FLEET_PROC", "1")
    monkeypatch.setenv("SAV_FLEET_PROCS", "2")
    config = _fit_config(tmp_path, log_every_steps=2)
    Trainer(config).fit(_batches(4), num_steps=4)
    records = read_heartbeats(str(tmp_path))
    assert list(records) == [1]  # its own stream, as proc 1
    assert records[1][0]["procs"] == 2
    # Shared artifacts belong to fleet process 0 — not written here.
    assert not os.path.exists(os.path.join(str(tmp_path), "goodput.json"))
    assert not os.path.exists(
        os.path.join(fleet_dir(str(tmp_path)), "fleet.json")
    )


def test_crashed_fit_stream_has_error_final(tmp_path, devices):
    config = _fit_config(tmp_path)
    trainer = Trainer(config)

    def exploding():
        yield from _batches(3)
        raise RuntimeError("iterator died")

    with pytest.raises(RuntimeError):
        trainer.fit(exploding(), num_steps=8)
    records = read_heartbeats(str(tmp_path))[0]
    assert records[-1]["kind"] == "final"
    assert records[-1]["outcome"] == "error"


# ------------------------------------------------------------- the tools


def test_fleet_status_cli_json_and_text(tmp_path, capsys):
    _write_stream(tmp_path, 0, _stream(0, per_step=1.0), final="ok")
    _write_stream(tmp_path, 1, _stream(1, per_step=4.0), final="ok")
    fleet_status = _load_tool("fleet_status")
    assert fleet_status.main(["--json", str(tmp_path)]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["straggler"]["straggler"] == 1
    assert fleet_status.main([str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "STRAGGLER" in text and "proc 1" in text
    assert fleet_status.main([str(tmp_path / "nope")]) == 2


def test_run_report_fleet_renders_and_degrades_gracefully(tmp_path):
    run_report = _load_tool("run_report")
    # No fleet dir: --fleet degrades to a note, exit 0 (the r7 battery
    # renders old runs too).
    out = io.StringIO()
    empty = tmp_path / "empty"
    empty.mkdir()
    assert run_report.main([str(empty), "--fleet"]) == 0
    run_report.report_fleet(str(empty), out)
    assert "no fleet directory" in out.getvalue()
    # With streams: processes + straggler rendered.
    _write_stream(tmp_path, 0, _stream(0, per_step=1.0), final="ok")
    _write_stream(tmp_path, 1, _stream(1, per_step=4.0))
    out = io.StringIO()
    run_report.report_fleet(str(tmp_path), out)
    text = out.getvalue()
    assert "2 process(es)" in text
    assert "STRAGGLER: proc 1" in text
    assert "no final record" in text
    # A fleet dir with no heartbeat streams (the backend never came up):
    # rendered, not crashed.
    empty_dir = tmp_path / "no_streams"
    (empty_dir / "fleet").mkdir(parents=True)
    out = io.StringIO()
    run_report.report_fleet(str(empty_dir), out)
    assert "no heartbeat streams" in out.getvalue()
