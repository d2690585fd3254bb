#!/usr/bin/env python
"""Render a run's telemetry into a human-readable summary.

Reads the files the trainer writes to its log dir (train.py --log-dir;
docs/observability.md):

  metrics.jsonl     — per-window step metrics (+ in-jit diagnostics)
  goodput.json      — wall-time ledger (compile/step/input-wait/... buckets
                      + roofline gauges: MFU, per-group FLOPs attribution)
  manifest.json     — run manifest (outcome taxonomy, env fingerprint)
  spans.trace.json  — host-side span trace (only its event count is shown
                      here; load the file itself in https://ui.perfetto.dev)

``--bench`` additionally renders bench-record history (driver
``BENCH_r*.json`` wrappers / raw bench lines / manifests) WITHOUT assuming
healthy inputs: ``rc != 0`` / ``parsed: null`` records land in an "infra
failures" section instead of crashing the report or being silently
skipped.

``--chain`` (or any log dir with a ``supervisor.json``) renders the
elastic-training supervisor's manifest chain (docs/elasticity.md):
attempts, restart reasons, resumed-from steps, lost time, skipped
batches, and the goodput accounting; single-attempt and unsupervised
runs degrade gracefully.

``--incidents`` (or any log dir that has an ``incidents/`` directory)
renders the flight recorder's bundles (``sav_tpu/obs/recorder.py``,
docs/incident_replay.md): step, trigger, replay window, and — when
``tools/replay_step.py`` has been run — the saved verdict (bit-exact
reproduction, first nonfinite layer group, checkify/f32 escalation),
so nobody has to spelunk ``.npz`` files to read an incident.

Stdlib-only (no jax import): safe to run on a laptop against rsynced logs.

Usage:
  python tools/run_report.py runs/vit_ti_patch16
  python tools/run_report.py --metrics some/metrics.jsonl
  python tools/run_report.py --bench BENCH_r*.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _REPO_ROOT)

# Stdlib-only modules (no jax) — the laptop-safety contract holds.
from sav_tpu.obs.fleet import (  # noqa: E402
    aggregate_fleet,
    fleet_dir,
    iter_manifests,
)
from sav_tpu.obs.manifest import load_run_history  # noqa: E402
from sav_tpu.obs.traceview import fleet_request_spans  # noqa: E402
from sav_tpu.serve.telemetry import (  # noqa: E402
    aggregate_serve,
    find_exemplars,
    find_serve_manifests,
)


def _fmt_seconds(s: float) -> str:
    if s >= 3600:
        return f"{s / 3600:.2f} h"
    if s >= 60:
        return f"{s / 60:.2f} min"
    return f"{s:.2f} s"


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(b) < 1024 or unit == "TiB":
            return f"{b:.1f} {unit}"
        b /= 1024
    return f"{b:.1f} TiB"


def load_metrics(path: str) -> list[dict]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail line of a crashed run
    return records


def _series(records: list[dict], key: str) -> list[tuple[int, float]]:
    out = []
    for r in records:
        v = r.get(key)
        if isinstance(v, (int, float)):
            out.append((int(r.get("step", 0)), float(v)))
    return out


def _stats_line(name: str, series: list[tuple[int, float]]) -> str:
    values = [v for _, v in series]
    lo, hi = min(values), max(values)
    return (
        f"  {name:<24} last {values[-1]:<12.6g} "
        f"min {lo:<12.6g} max {hi:<12.6g} ({len(values)} points)"
    )


def report_metrics(records: list[dict], out) -> None:
    train = [r for r in records if "loss" in r]
    evals = [r for r in records if "eval_top_1_acc" in r]
    print(f"Training windows logged: {len(train)}", file=out)
    if train:
        last = train[-1]
        print(f"Last logged step: {int(last.get('step', 0))}", file=out)
        for key in ("loss", "top_1_acc", "images_per_sec", "mfu"):
            s = _series(train, key)
            if s:
                print(_stats_line(key, s), file=out)
        print("Optimization diagnostics (--diagnostics):", file=out)
        diag_keys = [
            "grad_norm", "param_norm", "update_norm",
            "update_to_param_ratio", "nonfinite_grads", "retraces",
        ]
        for key in diag_keys:
            s = _series(train, key)
            if s:
                print(_stats_line(key, s), file=out)
        group_keys = sorted(
            {k for r in train for k in r if k.startswith("grad_norm/")}
        )
        for key in group_keys:
            s = _series(train, key)
            if s:
                print(_stats_line(key, s), file=out)
        if not _series(train, "param_norm"):
            print(
                "  (in-jit diagnostics absent — rerun with --diagnostics)",
                file=out,
            )
        hbm = _series(train, "hbm_peak_bytes")
        if hbm:
            print(
                f"  HBM peak: {_fmt_bytes(hbm[-1][1])} "
                f"(in use: {_fmt_bytes(_series(train, 'hbm_bytes_in_use')[-1][1])})",
                file=out,
            )
    if evals:
        best = max(evals, key=lambda r: r["eval_top_1_acc"])
        print(
            f"Eval: best top-1 {best['eval_top_1_acc']:.4f} at step "
            f"{int(best.get('step', 0))} (last "
            f"{evals[-1]['eval_top_1_acc']:.4f}, {len(evals)} passes)",
            file=out,
        )


def report_goodput(summary: dict, out) -> None:
    total = summary.get("wall_s", 0.0)
    print(
        f"Goodput ledger: {_fmt_seconds(total)} wall, "
        f"{summary.get('steps', 0)} steps, "
        f"goodput {summary.get('goodput_fraction', 0.0):.1%}",
        file=out,
    )
    buckets = summary.get("buckets_s", {})
    fractions = summary.get("fractions", {})
    for name, secs in sorted(buckets.items(), key=lambda kv: -kv[1]):
        if secs <= 0:
            continue
        bar = "#" * int(round(40 * fractions.get(name, 0.0)))
        print(
            f"  {name:<12} {_fmt_seconds(secs):>12} "
            f"{fractions.get(name, 0.0):>7.1%}  {bar}",
            file=out,
        )
    gauges = summary.get("gauges", {})
    feeder = {
        k[len("feeder/"):]: v
        for k, v in gauges.items() if k.startswith("feeder/")
    }
    if feeder:
        # Background-thread work the async feeder overlapped with device
        # compute — not wall-time buckets (the buckets above already sum
        # to wall). h2d_s hidden behind 'step' is the overlap win;
        # depth_avg ~ depth means the buffer stayed full (input-bound
        # runs sit near 0 instead).
        print(
            f"  async feeder: {int(feeder.get('batches', 0))} batches, "
            f"h2d {_fmt_seconds(feeder.get('h2d_s', 0.0))} + fetch "
            f"{_fmt_seconds(feeder.get('fetch_s', 0.0))} overlapped "
            f"(consumer waited {_fmt_seconds(feeder.get('wait_s', 0.0))}; "
            f"depth avg {feeder.get('depth_avg', 0.0):.2f}/"
            f"{int(feeder.get('depth', 0))}, "
            f"max {int(feeder.get('depth_max', 0))})",
            file=out,
        )
    # Roofline + per-group FLOPs attribution (obs/costs.py gauges): the
    # achieved-vs-peak number the 'fast as the hardware allows' north
    # star is falsified against, and where the step's FLOPs actually go.
    mfu = gauges.get("mfu")
    handled = {"mfu", "flops_per_s", "peak_flops", "peak_flops_is_fake",
               "flops/step_per_device"}
    if mfu is not None:
        fake = " (FAKE cpu peak — plumbing check, not a hardware number)" \
            if gauges.get("peak_flops_is_fake") else ""
        print(
            f"  Roofline: {mfu:.2%} MFU — "
            f"{gauges.get('flops_per_s', 0.0) / 1e9:.2f} GFLOP/s achieved "
            f"vs peak {gauges.get('peak_flops', 0.0) / 1e12:.1f} "
            f"TFLOP/s{fake}",
            file=out,
        )
    attrib = sorted(
        (k[len("flops/"):-len("_frac")], v)
        for k, v in gauges.items()
        if k.startswith("flops/") and k.endswith("_frac")
    )
    if attrib:
        print("  FLOPs attribution (analytic cost model):", file=out)
        for name, frac in sorted(attrib, key=lambda kv: -kv[1]):
            bar = "#" * int(round(40 * frac))
            print(f"    {name:<18} {frac:>7.1%}  {bar}", file=out)
        handled |= {f"flops/{name}_frac" for name, _ in attrib}
    other_gauges = {
        k: v for k, v in gauges.items()
        if not k.startswith("feeder/") and k not in handled
    }
    for name, value in sorted(other_gauges.items()):
        print(f"  gauge {name}: {value:g}", file=out)
    anomalies = summary.get("anomalies", [])
    if anomalies:
        print(f"  stall anomalies: {len(anomalies)}", file=out)
        for a in anomalies[:10]:
            print(
                f"    step {a.get('step')}: {a.get('per_step_s')}s/step "
                f"({a.get('slowdown')}x the {a.get('median_per_step_s')}s "
                "median)",
                file=out,
            )
        if len(anomalies) > 10:
            print(f"    ... and {len(anomalies) - 10} more", file=out)
    else:
        print("  no stall anomalies", file=out)


def report_manifest(doc: dict, out) -> None:
    outcome = doc.get("outcome", "?")
    flag = "" if outcome == "ok" else "  <-- NOT ok"
    print(
        f"Manifest: {doc.get('kind', 'run')} outcome={outcome}{flag}",
        file=out,
    )
    if doc.get("error"):
        print(f"  error: {doc['error']}", file=out)
    env = doc.get("env") or {}
    sha = env.get("git_sha")
    print(
        f"  env: git {sha[:10] if sha else '?'}, "
        f"python {env.get('python', '?')}, host {env.get('hostname', '?')}",
        file=out,
    )
    notes = doc.get("notes") or {}
    layout = notes.get("layout") or {}
    if layout:
        # "Which layout was this run" reads from this one line
        # (sav_tpu/parallel/layout.py SpecLayout.describe provenance).
        axes = layout.get("mesh_axes") or {}
        axes_s = " ".join(f"{a}={s}" for a, s in axes.items()) or "?"
        arms = []
        if layout.get("tp"):
            arms.append(
                f"{layout['tp']} tp over "
                + "+".join(layout.get("tp_axes") or [])
            )
        for key in ("fsdp_axis", "expert_axis", "pipe_axis", "seq_axis"):
            if layout.get(key):
                arms.append(f"{key.split('_')[0]} over {layout[key]}")
        print(
            f"  layout: {layout.get('name', '?')} [{axes_s}]"
            + (f" — {', '.join(arms)}" if arms else " — pure dp")
            + (
                f" (source {layout['source']})"
                if layout.get("source") else ""
            ),
            file=out,
        )
    if "seq_replication_fallback" in notes:
        info = notes["seq_replication_fallback"]
        print(
            f"  DEGRADED PARALLELISM: sequence-parallel batch replication "
            f"(batch {info.get('batch')} vs data-axis product "
            f"{info.get('data_axis_product')})",
            file=out,
        )
    device_check = (notes.get("device_check") or {})
    if device_check:
        print(f"  device check: {device_check.get('error')}", file=out)
    incidents = notes.get("incidents") or (
        [{"path": notes["incident"]}] if notes.get("incident") else []
    )
    if incidents:
        print(
            f"  INCIDENTS: {len(incidents)} flight-recorder bundle(s) — "
            "see the Incidents section / tools/replay_step.py",
            file=out,
        )
    hbm = notes.get("hbm") or {}
    peak = hbm.get("peak_bytes") or doc.get("metrics", {}).get(
        "hbm_peak_bytes"
    )
    if peak:
        print(
            f"  HBM watermark: {_fmt_bytes(float(peak))} peak "
            f"({hbm.get('source', '?')})",
            file=out,
        )
    if notes.get("memdump"):
        md = notes["memdump"]
        print(
            f"  MEMDUMP: memory-forensics bundle at step {md.get('step')} "
            f"({md.get('path')}) — see the Incidents section",
            file=out,
        )


def _render_memdump(name: str, bundle: str, out) -> None:
    """One memory-forensics bundle (sav_tpu/obs/memdump.py): live-buffer
    classes, the top resident buffers, and the watermark — the OOM
    post-mortem without spelunking a pprof."""
    try:
        with open(os.path.join(bundle, "memdump.json")) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        print(f"  {name}: (unreadable/torn memdump.json)", file=out)
        return
    live = doc.get("live") or {}
    wm = doc.get("watermark") or {}
    print(
        f"  {name}: {doc.get('trigger')} at step {doc.get('step')} — "
        f"{_fmt_bytes(live.get('total_bytes', 0.0))} live in "
        f"{live.get('num_buffers', 0)} buffers"
        + (
            f", watermark {_fmt_bytes(wm['peak_bytes'])} "
            f"({wm.get('source')})" if wm.get("peak_bytes") else ""
        )
        + (", pprof saved" if doc.get("pprof") else ""),
        file=out,
    )
    if doc.get("error"):
        print(f"    error: {str(doc['error'])[:120]}", file=out)
    classes = live.get("class_bytes") or {}
    if classes:
        print(
            "    by class: " + ", ".join(
                f"{cls} {_fmt_bytes(b)}"
                for cls, b in sorted(classes.items(), key=lambda kv: -kv[1])
                if b
            ),
            file=out,
        )
    for row in (live.get("buffers") or [])[:5]:
        group = f" [{row['group']}]" if row.get("group") else ""
        print(
            f"    {_fmt_bytes(row.get('bytes', 0.0)):>10} x"
            f"{row.get('count', 0):<4d} {row.get('class')}{group} "
            f"{row.get('dtype')}{row.get('shape')}",
            file=out,
        )


def report_traces(log_dir: str, out) -> None:
    """Render trace-intelligence summaries (docs/profiling.md): every
    autoprof capture's ``trace_summary.json`` plus bench's traced
    window, as measured-vs-predicted component tables."""
    import glob as _glob

    paths = sorted(
        _glob.glob(
            os.path.join(log_dir, "autoprof", "*", "trace_summary.json")
        )
    ) + sorted(
        _glob.glob(
            os.path.join(log_dir, "trace", "**", "trace_summary.json"),
            recursive=True,
        )
    )
    if not paths:
        print(
            f"(no trace summaries under {log_dir} — capture with "
            "--autoprof / bench --trace, or run tools/trace_report.py "
            "on a raw trace)",
            file=out,
        )
        return
    print(f"Trace summaries: {len(paths)}", file=out)
    for path in paths:
        try:
            with open(path) as f:
                s = json.load(f)
        except (OSError, json.JSONDecodeError):
            print(f"  {path}: (unreadable/torn)", file=out)
            continue
        rel = os.path.relpath(path, log_dir)
        idle = s.get("idle_frac")
        acf = s.get("attention_core_frac")
        print(
            f"  {os.path.dirname(rel)}: {s.get('per_step_ms')} ms/step "
            f"device time ({s.get('device_selector')}, indexed "
            f"{s.get('indexed_frac', 0.0):.0%}"
            + (f", idle {idle:.0%}" if idle is not None else "")
            + (f", attention core {acf:.1%}" if acf is not None else "")
            + ")",
            file=out,
        )
        vs = s.get("vs_predicted")
        if vs:
            for row in vs.get("rows", []):
                flag = "  <-- DISAGREES" if row.get("flagged") else ""
                print(
                    f"    {row['component']:<16} measured "
                    f"{row['measured_frac']:>7.1%}  predicted "
                    f"{row['predicted_frac']:>7.1%}{flag}",
                    file=out,
                )
        else:
            comps = ", ".join(
                f"{k} {v:.0%}"
                for k, v in sorted(
                    (s.get("components_frac") or {}).items(),
                    key=lambda kv: -kv[1],
                )
                if v
            )
            if comps:
                print(f"    {comps}", file=out)


def report_incidents(log_dir: str, out) -> None:
    """Render the flight recorder's incident directory + replay verdicts."""
    root = os.path.join(log_dir, "incidents")
    if not os.path.isdir(root):
        print(f"(no incidents directory at {root})", file=out)
        return
    bundles = sorted(
        d for d in os.listdir(root)
        if os.path.isdir(os.path.join(root, d))
    )
    print(f"Incidents: {len(bundles)} bundle(s) under {root}", file=out)
    for name in bundles:
        bundle = os.path.join(root, name)
        if name.startswith("memdump_"):
            _render_memdump(name, bundle, out)
            continue
        try:
            with open(os.path.join(bundle, "incident.json")) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            print(f"  {name}: (unreadable/torn incident.json)", file=out)
            continue
        batches = doc.get("batch_steps") or []
        snap = doc.get("snapshot_step")
        print(
            f"  step {doc.get('step')}: trigger={doc.get('trigger')} "
            f"(snapshot {snap if snap is not None else '-'}; "
            f"{len(batches)} batch(es) kept; "
            f"{'replayable' if doc.get('replayable') else 'NOT replayable'})",
            file=out,
        )
        if doc.get("error"):
            print(f"    error: {str(doc['error'])[:120]}", file=out)
        verdict_path = os.path.join(bundle, "replay_verdict.json")
        if not os.path.exists(verdict_path):
            if doc.get("replayable"):
                print(
                    f"    (no replay verdict — run: python "
                    f"tools/replay_step.py {bundle})",
                    file=out,
                )
            continue
        try:
            with open(verdict_path) as f:
                verdict = json.load(f)
        except (OSError, json.JSONDecodeError):
            print("    (unreadable/torn replay_verdict.json)", file=out)
            continue
        exact = (
            "bit-exact" if verdict.get("metrics_match")
            else "MISMATCHED"
        )
        print(
            f"    replay: {len(verdict.get('replayed_steps') or [])} "
            f"step(s), recorded metrics {exact}; first nonfinite step "
            f"{verdict.get('first_bad_step')}, first bad layer group "
            f"{verdict.get('first_bad_group')}",
            file=out,
        )
        checkify = verdict.get("checkify") or {}
        if checkify.get("first_error"):
            print(f"    checkify: {checkify['first_error'][:120]}", file=out)
        f32 = verdict.get("f32") or {}
        if f32.get("ran"):
            print(
                "    f32 recompute: "
                + ("finite — bf16 range/precision implicated"
                   if f32.get("finite")
                   else "still nonfinite — genuine divergence"),
                file=out,
            )


def report_fleet(log_dir: str, out) -> None:
    """Render the fleet-telemetry summary (docs/fleet.md): per-process
    heartbeats, step skew, straggler ranking and dead-host suspicion.
    Degrades gracefully — a run with no
    ``fleet/`` dir (fleet telemetry off, or predating it) reports that
    instead of erroring."""
    if not os.path.isdir(fleet_dir(log_dir)):
        print(f"(no fleet directory at {fleet_dir(log_dir)} — run with "
              "fleet telemetry on)", file=out)
        return
    summary = aggregate_fleet(log_dir)
    processes = summary.get("processes") or {}
    if not processes:
        print(
            f"Fleet: no heartbeat streams under {fleet_dir(log_dir)}",
            file=out,
        )
        return
    finals = sum(1 for v in processes.values() if v.get("final"))
    print(
        f"Fleet: {len(processes)} process(es), {finals} with final "
        "records",
        file=out,
    )
    for proc in sorted(processes, key=int):
        v = processes[proc]
        med = v.get("median_step_s")
        print(
            f"  proc {proc}: {v.get('heartbeats', 0)} heartbeats, last "
            f"step {v.get('last_step')}"
            + (f", median {med:g} s/step" if med is not None else "")
            + ("" if v.get("final") else "  <-- no final record"),
            file=out,
        )
    skew = summary.get("step_skew") or {}
    if skew.get("skew"):
        print(
            f"  step skew: {skew['skew']} (laggard proc "
            f"{skew.get('laggard')})",
            file=out,
        )
    straggler = (summary.get("straggler") or {}).get("straggler")
    if straggler is not None:
        print(f"  STRAGGLER: proc {straggler} (see tools/fleet_status.py "
              f"{log_dir} for the ranking)", file=out)
    for s in summary.get("suspects") or []:
        print(
            f"  SUSPECT DEAD: proc {s['proc']} stopped heartbeating at "
            f"step {s.get('last_step')} (silent {s.get('silent_s')}s)",
            file=out,
        )
    for e in summary.get("events") or []:
        print(
            f"  event: proc {e.get('proc')} {e.get('event')} at step "
            f"{e.get('step')}",
            file=out,
        )


def report_serve(log_dir: str, out, manifests: list = None) -> None:
    """Render the serve-telemetry view (docs/serving.md): kind=serve
    manifests, the windowed heartbeat headline per replica, SLO burn
    state, and the slow-request exemplar index. Degrades gracefully — a
    PR-10-era serve dir (manifest, no telemetry artifacts) renders its
    manifest and notes the missing telemetry instead of erroring.
    ``manifests`` takes the already-loaded kind=serve manifest list
    (main()'s auto-detect globs+parses them — don't pay it twice)."""
    if manifests is None:
        manifests = find_serve_manifests(log_dir)
    serve = aggregate_serve(log_dir)
    replicas = serve.get("replicas") or {}
    exemplars = find_exemplars(log_dir)
    # notes.serve_traces lives on the kind=serve_fleet manifest (the
    # fleet bench's), which find_serve_manifests (kind=serve only)
    # deliberately excludes — scan the full manifest set for it. Found
    # traces keep a fleet-only dir (no per-replica serve manifests)
    # from reading as "no serve telemetry".
    trace_notes = []
    quality_notes = []
    for _, doc in iter_manifests(log_dir):
        notes = doc.get("notes") or {}
        if isinstance(notes.get("serve_traces"), dict):
            trace_notes.append(notes["serve_traces"])
        # notes.quality rides the fleet bench's kind=serve_fleet
        # manifest (shadow agreement fold) and the engine's kind=serve
        # manifest (digest/probe snapshot) — ISSUE 20.
        if isinstance(notes.get("quality"), dict):
            quality_notes.append(notes["quality"])
    router_export = os.path.join(
        log_dir, "serve_traces", "requests_router.trace.json.gz"
    )
    has_fleet_traces = bool(trace_notes) or os.path.isfile(router_export)
    if not manifests and not replicas and not has_fleet_traces:
        print(f"(no serve telemetry under {log_dir})", file=out)
        return
    for m in manifests:
        metrics = m.get("metrics") or {}
        outcome = m.get("outcome", "?")
        flag = "" if outcome in ("ok", "running") else "  <-- NOT ok"
        print(
            f"Serve manifest {os.path.basename(m.get('path') or '?')}: "
            f"outcome={outcome}{flag}",
            file=out,
        )
        p99 = metrics.get("serve/p99_latency_ms")
        if p99 is not None:
            slo = metrics.get("serve/slo_hit_frac")
            print(
                f"  p99 {p99} ms, {metrics.get('serve/throughput_rps')} "
                "req/s"
                + (f", SLO hit {slo:.2%}" if slo is not None else "")
                + (
                    f", burn rate {metrics.get('serve/burn_rate')}"
                    if metrics.get("serve/burn_rate") is not None else ""
                ),
                file=out,
            )
        # Prediction-quality stamps (ISSUE 20, docs/quality.md):
        # golden-probe health, present only on probe-instrumented runs.
        pok = metrics.get("serve/probe_ok_frac")
        if pok is not None:
            flag = "" if pok >= 1.0 else "  <-- PROBE MISMATCH"
            print(f"  golden probes: {pok:.0%} ok{flag}", file=out)
    if replicas:
        for proc in sorted(replicas, key=int):
            v = replicas[proc]
            flame = "  <-- SLO BURNING" if v.get("burning") else ""
            print(
                f"  serve replica {proc}: {v.get('beats')} heartbeats — "
                f"windowed p99 {v.get('p99_ms')} ms, "
                f"{v.get('throughput_rps')} req/s, queue "
                f"{v.get('queue_depth')}, shed {v.get('shed')}{flame}",
                file=out,
            )
    else:
        print(
            "  (no serve telemetry — heartbeats/windows/exemplars need "
            "an r11+ engine with telemetry on)",
            file=out,
        )
    # Capacity/headroom + alert episodes (ISSUE 19): the fleet fold
    # carries summed capacity_rps stamps vs the load projection, and
    # fleet/alerts.jsonl carries the declarative rule engine's events.
    fleet_fold = serve.get("fleet") or {}
    # Quality fold (ISSUE 20): worst-replica probe health across the
    # fleet — skip-not-zero-fill, like capacity.
    if fleet_fold.get("probe_ok_frac") is not None:
        pfrac = fleet_fold["probe_ok_frac"]
        pflag = "" if pfrac >= 1.0 else "  <-- PROBE MISMATCH"
        print(
            f"  probe health: worst replica {pfrac:.0%} ok{pflag}",
            file=out,
        )
    if fleet_fold.get("capacity_rps") is not None:
        head = fleet_fold.get("headroom_frac")
        print(
            f"  capacity {fleet_fold['capacity_rps']} req/s"
            + (
                f", projected load {fleet_fold['projected_rps']} req/s"
                if fleet_fold.get("projected_rps") is not None else ""
            )
            + (f", headroom {head:.1%}" if head is not None else ""),
            file=out,
        )
    for note in quality_notes:
        shadow = note.get("shadow") or {}
        if shadow.get("scored"):
            agreement = shadow.get("agreement")
            print(
                f"  shadow agreement: rank {shadow.get('rank')} "
                f"[{shadow.get('dtype') or '?'}], "
                f"{shadow.get('scored')} scored, "
                + (
                    f"agreement {agreement:.2%}"
                    if isinstance(agreement, (int, float)) else
                    "agreement —"
                )
                + f", {shadow.get('breach', 0)} breach(es)",
                file=out,
            )
    from sav_tpu.obs.alerts import episodes as _alert_eps, read_alerts

    for rule, entry in sorted(_alert_eps(read_alerts(log_dir)).items()):
        state = "FIRING" if entry.get("active") else "resolved"
        print(
            f"  alert {rule} [{entry.get('severity')}]: {state}, "
            f"{entry.get('fired')} episode(s)",
            file=out,
        )
    if exemplars:
        print(
            f"  slow-request exemplars: {len(exemplars)} "
            f"(see tools/serve_status.py {log_dir})",
            file=out,
        )
        for e in exemplars[:5]:
            where = " [fleet walk]" if e.get("fleet") else ""
            print(
                f"    req {e.get('rid')}: {e.get('latency_ms')} ms "
                f"(overrun {e.get('overrun_ms')} ms) — "
                f"{e.get('dominant_stage')} dominated{where}",
                file=out,
            )
    # Fleet trace section (ISSUE 16): render the notes.serve_traces
    # pointers the fleet bench stamped, plus the merged-trace headline
    # (clock offsets + dominant fleet stages) when the merge is on
    # disk or derivable.
    merged_path = os.path.join(
        log_dir, "serve_traces", "fleet.trace.json.gz"
    )
    if has_fleet_traces:
        for note in trace_notes:
            n_rep = len(note.get("replicas") or [])
            print(
                "  distributed traces: router export "
                + ("yes" if note.get("router") else "MISSING")
                + f", {n_rep} replica export(s), merged "
                + (
                    os.path.basename(note["merged"])
                    if note.get("merged") else "MISSING"
                )
                + f", {note.get('fleet_exemplars', 0)} fleet exemplar(s)",
                file=out,
            )
        try:
            fleet = fleet_request_spans(log_dir)
        except (OSError, ValueError, KeyError, TypeError):
            fleet = {"requests": {}, "replicas": {}}
        if fleet.get("requests"):
            dom: dict = {}
            router_only = 0
            for entry in fleet["requests"].values():
                ds = entry.get("dominant_stage")
                if ds:
                    dom[ds] = dom.get(ds, 0) + 1
                if entry.get("router_only"):
                    router_only += 1
            dom_s = ", ".join(
                f"{k} x{v}"
                for k, v in sorted(dom.items(), key=lambda kv: -kv[1])
            )
            skews = [
                est.get("skew_ms") for est in fleet["replicas"].values()
                if isinstance(est.get("skew_ms"), (int, float))
            ]
            print(
                f"  merged fleet trace: {len(fleet['requests'])} "
                f"request walk(s)"
                + (
                    f", clock skew bound +/-{max(skews)} ms"
                    if skews else ""
                )
                + (
                    f", {router_only} router-only (degraded)"
                    if router_only else ""
                )
                + (f" — dominant stages: {dom_s}" if dom_s else "")
                + (
                    f" (see tools/trace_report.py {merged_path})"
                    if os.path.isfile(merged_path) else ""
                ),
                file=out,
            )


def report_chain(log_dir: str, out) -> None:
    """Render a supervisor manifest chain (docs/elasticity.md):
    attempts, restart reasons, resumed-from steps, lost time, skipped
    batches, and the goodput accounting. Degrades gracefully: a
    single-attempt chain reads as "no restarts", and a run that was
    never supervised reports that instead of erroring."""
    path = os.path.join(log_dir, "supervisor.json")
    if not os.path.exists(path):
        print(f"(no supervisor chain at {path} — run with --supervise)",
              file=out)
        return
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        print(f"Supervisor chain: {path} (unreadable/torn)", file=out)
        return
    chain = (doc.get("notes") or {}).get("chain") or {}
    attempts = chain.get("attempts") or []
    goodput = chain.get("goodput") or {}
    outcome = doc.get("outcome", "?")
    flag = "" if outcome == "ok" else "  <-- NOT ok"
    print(
        f"Supervisor chain: {len(attempts)} attempt(s), "
        f"outcome={outcome}{flag}",
        file=out,
    )
    if doc.get("error"):
        print(f"  error: {doc['error']}", file=out)
    for a in attempts:
        reason = a.get("restart_reason")
        lost = a.get("lost_s")
        print(
            f"  attempt {a.get('attempt')}: steps "
            f"{a.get('resumed_from_step')} -> {a.get('last_step')}, "
            f"{_fmt_seconds(a.get('wall_s') or 0.0)} wall, "
            + (
                f"lost {_fmt_seconds(lost)}"
                if isinstance(lost, (int, float)) and lost else "no loss"
            )
            + (f"  [{reason}]" if reason else "  [finished]"),
            file=out,
        )
        if a.get("skip_decided"):
            print(
                f"    rewind-and-skip decided here: step(s) "
                f"{a['skip_decided']}",
                file=out,
            )
        if a.get("skip_steps"):
            print(
                f"    skip set armed: step(s) {a['skip_steps']}",
                file=out,
            )
    if len(attempts) == 1:
        print("  (single attempt — no restarts were needed)", file=out)
    skipped = chain.get("skipped_steps") or []
    if skipped:
        print(f"  skipped batches (once each): {skipped}", file=out)
    if goodput:
        print(
            f"  goodput: {goodput.get('goodput_frac', 0.0):.1%} "
            f"({_fmt_seconds(goodput.get('lost_s', 0.0))} lost + "
            f"{_fmt_seconds(goodput.get('backoff_s', 0.0))} backoff over "
            f"{_fmt_seconds(goodput.get('wall_s', 0.0))} wall; "
            f"accounting covers "
            f"{goodput.get('accounted_frac', 0.0):.1%})",
            file=out,
        )


def report_bench_history(paths: list, out) -> int:
    """Render bench-record history; returns a process exit code (2 on
    unreadable input — mirroring the sentinel's usage/IO contract)."""
    try:
        records = load_run_history(paths)
    except (OSError, ValueError) as e:
        print(f"cannot read bench records: {e}", file=sys.stderr)
        return 2
    ok = [r for r in records if r.ok]
    infra = [r for r in records if not r.ok]
    print(
        f"Bench history: {len(records)} records — {len(ok)} measurements, "
        f"{len(infra)} infra failures",
        file=out,
    )
    for r in ok:
        mfu = r.metrics.get("mfu")
        extra = f", mfu {mfu:.2%}" if mfu is not None else ""
        print(
            f"  ok      {r.label}: "
            f"{r.metrics.get('throughput', float('nan')):g} img/s/chip"
            f"{extra}",
            file=out,
        )
    if infra:
        # rc != 0 / parsed: null records are INFRA, not measurements —
        # listed, never averaged, never fatal to the report.
        print("  infra failures (excluded from any statistics):", file=out)
        for r in infra:
            print(f"    {r.label}: {r.outcome} ({r.detail})", file=out)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    parser.add_argument(
        "log_dir", nargs="?", default=None,
        help="run log dir containing metrics.jsonl / goodput.json",
    )
    parser.add_argument("--metrics", default=None, help="explicit metrics.jsonl")
    parser.add_argument("--goodput", default=None, help="explicit goodput.json")
    parser.add_argument(
        "--bench", nargs="+", default=None, metavar="RECORD",
        help="bench record files (BENCH_r*.json wrappers, raw bench JSON "
        "lines, manifests): rendered with infra failures separated",
    )
    parser.add_argument(
        "--fleet", action="store_true",
        help="render the log dir's fleet telemetry (heartbeat streams, "
        "step skew, straggler ranking, dead-host suspicion — "
        "docs/fleet.md); also rendered automatically when a fleet/ "
        "directory exists. Degrades gracefully on runs without one.",
    )
    parser.add_argument(
        "--trace", action="store_true",
        help="render the log dir's trace-intelligence summaries "
        "(autoprof captures' trace_summary.json, bench --trace windows) "
        "as measured-vs-predicted attribution tables "
        "(docs/profiling.md); also rendered automatically when an "
        "autoprof/ directory exists",
    )
    parser.add_argument(
        "--chain", action="store_true",
        help="render the log dir's supervisor manifest chain "
        "(supervisor.json — train.py --supervise; docs/elasticity.md): "
        "attempts, restart reasons, lost time, skipped batches; also "
        "rendered automatically when the file exists. Degrades "
        "gracefully on single-attempt and unsupervised runs.",
    )
    parser.add_argument(
        "--incidents", action="store_true",
        help="render the log dir's flight-recorder incident bundles "
        "(<log-dir>/incidents/) with their replay verdicts; incident "
        "bundles are also rendered automatically when the directory "
        "exists",
    )
    parser.add_argument(
        "--serve", action="store_true",
        help="render the log dir's serve telemetry (kind=serve "
        "manifests, windowed heartbeat headline, SLO burn state, "
        "slow-request exemplars — docs/serving.md); also rendered "
        "automatically when a kind=serve manifest or serve heartbeat "
        "stream exists. PR-10-era serve dirs degrade to a '(no serve "
        "telemetry)' note.",
    )
    args = parser.parse_args(argv)
    if (
        args.log_dir is None and args.metrics is None
        and args.goodput is None and args.bench is None
    ):
        parser.error("pass a log dir, --metrics, --goodput, or --bench")
    if args.incidents and args.log_dir is None:
        if args.bench is None:
            parser.error("--incidents needs a log dir to look under")
        # --bench without a log dir: render the history, just note the
        # flag had nothing to point at instead of aborting the report.
        print("(--incidents ignored: no log dir given)", file=sys.stderr)
    if args.fleet and args.log_dir is None:
        if args.bench is None:
            parser.error("--fleet needs a log dir to look under")
        print("(--fleet ignored: no log dir given)", file=sys.stderr)
    if args.trace and args.log_dir is None:
        if args.bench is None:
            parser.error("--trace needs a log dir to look under")
        print("(--trace ignored: no log dir given)", file=sys.stderr)
    if args.chain and args.log_dir is None:
        if args.bench is None:
            parser.error("--chain needs a log dir to look under")
        print("(--chain ignored: no log dir given)", file=sys.stderr)
    if args.serve and args.log_dir is None:
        if args.bench is None:
            parser.error("--serve needs a log dir to look under")
        print("(--serve ignored: no log dir given)", file=sys.stderr)

    if args.bench:
        rc = report_bench_history(args.bench, sys.stdout)
        if rc or (
            args.log_dir is None and args.metrics is None
            and args.goodput is None
        ):
            return rc

    metrics_path = args.metrics or (
        os.path.join(args.log_dir, "metrics.jsonl") if args.log_dir else None
    )
    goodput_path = args.goodput or (
        os.path.join(args.log_dir, "goodput.json") if args.log_dir else None
    )
    out = sys.stdout
    if args.log_dir:
        print(f"== Run report: {args.log_dir} ==", file=out)

    if metrics_path and os.path.exists(metrics_path):
        report_metrics(load_metrics(metrics_path), out)
    elif metrics_path:
        print(f"(no metrics file at {metrics_path})", file=out)

    if goodput_path and os.path.exists(goodput_path):
        with open(goodput_path) as f:
            report_goodput(json.load(f), out)
    elif goodput_path:
        print(f"(no goodput ledger at {goodput_path})", file=out)

    if args.log_dir:
        manifest_path = os.path.join(args.log_dir, "manifest.json")
        if os.path.exists(manifest_path):
            try:
                with open(manifest_path) as f:
                    report_manifest(json.load(f), out)
            except json.JSONDecodeError:
                print(f"Manifest: {manifest_path} (unreadable/torn)", file=out)

    if args.log_dir and (
        args.chain
        or os.path.exists(os.path.join(args.log_dir, "supervisor.json"))
    ):
        report_chain(args.log_dir, out)

    if args.log_dir and (
        args.incidents
        or os.path.isdir(os.path.join(args.log_dir, "incidents"))
    ):
        report_incidents(args.log_dir, out)

    serve_manifests = (
        find_serve_manifests(args.log_dir) if args.log_dir else []
    )
    if args.log_dir and (
        args.serve
        or os.path.isdir(os.path.join(args.log_dir, "serve_traces"))
        or serve_manifests
    ):
        report_serve(args.log_dir, out, manifests=serve_manifests)

    if args.log_dir and (
        args.trace or os.path.isdir(os.path.join(args.log_dir, "autoprof"))
    ):
        report_traces(args.log_dir, out)

    if args.log_dir and (
        args.fleet or os.path.isdir(fleet_dir(args.log_dir))
    ):
        report_fleet(args.log_dir, out)

    if args.log_dir:
        spans = os.path.join(args.log_dir, "spans.trace.json")
        if os.path.exists(spans):
            try:
                with open(spans) as f:
                    n = len(json.load(f).get("traceEvents", []))
                print(
                    f"Span trace: {spans} ({n} events) — load it in "
                    "https://ui.perfetto.dev",
                    file=out,
                )
            except json.JSONDecodeError:
                print(f"Span trace: {spans} (unreadable/torn)", file=out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
