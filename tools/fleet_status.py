#!/usr/bin/env python
"""Render a run's fleet telemetry: skew, stragglers, gaps, captures.

Reads the ``<log_dir>/fleet/`` artifact layout the trainer's heartbeat
writer produces (``sav_tpu/obs/fleet.py``, docs/fleet.md):

  proc_<i>.jsonl       per-process heartbeat streams
  fleet.json           merged fleet manifest (process 0's in-run view)

and re-aggregates the streams offline — the rendered straggler ranking /
dead-host suspicion always reflects the COMPLETE streams, not the
partial view process 0 had when it finished. Also lists anomaly-profiler
captures — the run manifest's ``notes.autoprof`` merged with every
process's ``autoprof/proc*_captures.jsonl`` sidecar (non-zero processes
run with a disabled manifest, so the straggler's own trace lives only
in its sidecar).

Stdlib-only (no jax import): safe to run on a laptop against rsynced
logs, and safe in the backend-unreachable post-mortem where importing
jax is exactly what hangs.

Usage:
  python tools/fleet_status.py runs/deit_s_patch16
  python tools/fleet_status.py --json runs/deit_s_patch16
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
    format_unix as _fmt_unix,
    read_autoprof_captures as autoprof_captures,
    read_router_beats,
)
from sav_tpu.serve.telemetry import aggregate_serve  # noqa: E402


def read_layout_notes(log_dir: str) -> list:
    """Every manifest's ``notes.layout`` under the log dir — the
    SpecLayout provenance stamp (mesh shape, axis sizes, preset source)
    the trainer and the serve engine write, so "which layout was this
    run" reads from the same artifact set as the heartbeats."""
    from sav_tpu.obs.fleet import iter_manifests

    layouts = []
    for path, doc in iter_manifests(log_dir):
        note = (doc.get("notes") or {}).get("layout")
        if isinstance(note, dict):
            layouts.append({"manifest": os.path.basename(path), **note})
    return layouts


def render(log_dir: str, summary: dict, out) -> None:
    processes = summary.get("processes") or {}
    print(f"== Fleet status: {log_dir} ==", file=out)
    if not processes:
        print(
            f"(no heartbeat streams under {fleet_dir(log_dir)} — run with "
            "fleet telemetry on, or the backend never came up: see the "
            "run manifest's outcome)",
            file=out,
        )
    else:
        print(f"Processes: {len(processes)}", file=out)
        for proc in sorted(processes, key=int):
            v = processes[proc]
            status = (
                f"final ({v.get('outcome')})" if v.get("final")
                else "no final record"
            )
            med = v.get("median_step_s")
            stall = v.get("median_host_stall_frac")
            print(
                f"  proc {proc} [{v.get('host', '?')}]: "
                f"{v.get('heartbeats', 0)} heartbeats, last step "
                f"{v.get('last_step')} at {_fmt_unix(v.get('last_unix'))}, "
                f"median {med if med is not None else '?'} s/step"
                + (f", host-stall {stall:.1%}" if stall is not None else "")
                + f" — {status}",
                file=out,
            )
            if v.get("incident"):
                print(f"    incident: {v['incident']}", file=out)
        skew = summary.get("step_skew") or {}
        if skew:
            print(
                f"Step skew: {skew.get('skew', 0)} steps "
                f"(frontier {skew.get('max_step')}, laggard proc "
                f"{skew.get('laggard')} at {skew.get('min_step')})",
                file=out,
            )
        timeline = summary.get("skew_timeline") or []
        if timeline:
            t0 = timeline[0].get("t", 0.0)
            tail = timeline[-8:]
            print(
                "Skew timeline (tail): "
                + "  ".join(
                    f"+{e.get('t', 0.0) - t0:.0f}s p{e.get('proc')}@"
                    f"{e.get('step')}"
                    for e in tail
                ),
                file=out,
            )
        straggler = summary.get("straggler") or {}
        ranking = straggler.get("ranking") or []
        if ranking:
            print("Straggler ranking (leave-one-out median+MAD):", file=out)
            for entry in ranking:
                flag = "  <-- STRAGGLER" if entry.get("flagged") else ""
                host_stall = (entry.get("host_stall") or {}).get("value")
                step_time = (entry.get("step_time") or {}).get("value")
                print(
                    f"  proc {entry['proc']}: score {entry.get('score')}"
                    + (
                        f", host-stall {host_stall:.1%}"
                        if host_stall is not None else ""
                    )
                    + (
                        f", {step_time:.4g} s/step"
                        if step_time is not None else ""
                    )
                    + flag,
                    file=out,
                )
        suspects = summary.get("suspects") or []
        for s in suspects:
            print(
                f"SUSPECT DEAD: proc {s['proc']} stopped heartbeating at "
                f"step {s.get('last_step')} "
                f"({_fmt_unix(s.get('last_unix'))}; silent "
                f"{s.get('silent_s')}s vs median interval "
                f"{s.get('median_interval_s')}s)",
                file=out,
            )
        events = summary.get("events") or []
        if events:
            print(f"Events: {len(events)}", file=out)
            for e in events[:10]:
                print(
                    f"  proc {e.get('proc')} {e.get('event')} at "
                    f"step {e.get('step')} ({_fmt_unix(e.get('t'))})",
                    file=out,
                )
    serve = summary.get("serve") or {}
    replicas = serve.get("replicas") or {}
    if replicas:
        # kind=serve heartbeat streams (sav_tpu/serve/telemetry.py):
        # the per-replica router view — windowed p99 / queue / occupancy
        # per process (full detail: tools/serve_status.py).
        fleet_line = serve.get("fleet") or {}
        print(
            f"Serve replicas: {len(replicas)} "
            f"({fleet_line.get('throughput_rps')} req/s total, worst p99 "
            f"{fleet_line.get('worst_p99_ms')} ms)",
            file=out,
        )
        for proc in sorted(replicas, key=int):
            v = replicas[proc]
            occ = v.get("occupancy")
            flame = "  <-- SLO BURNING" if v.get("burning") else ""
            dtype = f" [{v['dtype']}]" if v.get("dtype") else ""
            print(
                f"  replica {proc}{dtype}: p99 {v.get('p99_ms')} ms, "
                f"{v.get('throughput_rps')} req/s, queue "
                f"{v.get('queue_depth')}, inflight {v.get('inflight')}"
                + (f", occupancy {occ:.0%}" if occ is not None else "")
                + f", shed {v.get('shed')}{flame}",
                file=out,
            )
            # Prediction-quality beat fields (ISSUE 20): probe health,
            # present only on probe-instrumented replicas.
            q = v.get("quality") or {}
            if q.get("probe_runs"):
                miss = q.get("probe_mismatch") or 0
                print(
                    f"    probes: {q.get('probe_ok', 0)}/"
                    f"{q['probe_runs']} ok"
                    + (f", {miss} MISMATCH" if miss else "")
                    + (
                        f", {q['probe_shed']} shed"
                        if q.get("probe_shed") else ""
                    ),
                    file=out,
                )
        # Capacity/headroom fold (ISSUE 19): summed measured
        # capacity_rps stamps vs the Theil-Sen load projection.
        # Quality fold (ISSUE 20): worst-replica probe health.
        if fleet_line.get("probe_ok_frac") is not None:
            pfrac = fleet_line["probe_ok_frac"]
            pflag = "" if pfrac >= 1.0 else "  <-- PROBE MISMATCH"
            print(
                f"  probe health: worst replica {pfrac:.0%} ok{pflag}",
                file=out,
            )
        if fleet_line.get("capacity_rps") is not None:
            head = fleet_line.get("headroom_frac")
            print(
                f"  capacity {fleet_line['capacity_rps']} req/s"
                + (
                    f", projected load {fleet_line['projected_rps']} req/s"
                    if fleet_line.get("projected_rps") is not None else ""
                )
                + (f", headroom {head:.1%}" if head is not None else ""),
                file=out,
            )
    # Alert episodes (ISSUE 19): the declarative rule engine's
    # fleet/alerts.jsonl stream folded to per-rule accounting.
    from sav_tpu.obs.alerts import episodes, read_alerts

    for rule, entry in sorted(episodes(read_alerts(log_dir)).items()):
        state = "FIRING" if entry.get("active") else "resolved"
        print(
            f"  alert {rule} [{entry.get('severity')}]: {state}, "
            f"{entry.get('fired')} episode(s), last at "
            f"{_fmt_unix(entry.get('last_t'))}",
            file=out,
        )
    # kind=router heartbeat stream (ISSUE 16): the fleet router is a
    # first-class fleet citizen — its live windowed view renders next
    # to the replicas it balances (full detail: tools/serve_status.py).
    router_beats = read_router_beats(log_dir, tail_bytes=262_144)
    if router_beats:
        live = router_beats[-1]
        w = live.get("w") or {}
        print(
            f"Router: {len(router_beats)} heartbeat(s) — "
            f"{live.get('completed')} completed, p99 {w.get('p99_ms')} ms "
            f"@ {live.get('throughput_rps')} req/s, "
            f"{live.get('rerouted')} rerouted, {live.get('shed')} shed, "
            f"{live.get('down_flaps')} down-flaps, view age "
            f"{live.get('view_age_s')}s, trace overhead "
            f"{live.get('router_overhead_ms')} ms/req",
            file=out,
        )
        # Shadow agreement (ISSUE 20): the router's live quality fold.
        shadow = live.get("shadow")
        if shadow and shadow.get("scored"):
            agreement = shadow.get("agreement")
            print(
                f"  shadow rank {shadow.get('rank')} "
                f"[{shadow.get('dtype') or '?'}]: "
                f"{shadow.get('scored')} scored, "
                + (
                    f"agreement {agreement:.2%}"
                    if isinstance(agreement, (int, float)) else
                    "agreement —"
                )
                + f", {shadow.get('breach', 0)} breach(es)",
                file=out,
            )
    layouts = read_layout_notes(log_dir)
    if layouts:
        print(f"Layouts: {len(layouts)} manifest(s)", file=out)
        for note in layouts:
            axes = note.get("mesh_axes") or {}
            axes_s = " ".join(f"{a}={s}" for a, s in axes.items()) or "?"
            tp = note.get("tp")
            print(
                f"  {note.get('manifest')}: {note.get('name', '?')} "
                f"[{axes_s}]"
                + (
                    f", {tp} tp over "
                    + "+".join(note.get("tp_axes") or []) if tp else ""
                )
                + (
                    f", source {note['source']}"
                    if note.get("source") else ""
                ),
                file=out,
            )
    captures = autoprof_captures(log_dir)
    if captures:
        print(f"Autoprof captures: {len(captures)}", file=out)
        for c in captures:
            print(
                f"  {c.get('trigger')} at step {c.get('trigger_step')}: "
                f"steps {c.get('start_step')}..{c.get('end_step')} -> "
                f"{c.get('path')}",
                file=out,
            )
            s = c.get("summary") or {}
            if s:
                # Post-capture trace intelligence (obs/traceview.py):
                # the capture is already machine-read — render the
                # attribution headline instead of just the blob path.
                acf = s.get("attention_core_frac")
                disagrees = s.get("disagrees") or []
                print(
                    f"    {s.get('per_step_ms')} ms/step device time, "
                    f"indexed {s.get('indexed_frac', 0.0):.0%}"
                    + (
                        f", attention core {acf:.1%}"
                        if acf is not None else ""
                    )
                    + (
                        "; DISAGREES with cost model: "
                        + ", ".join(disagrees) if disagrees else ""
                    ),
                    file=out,
                )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "log_dir", help="run log dir (the parent of its fleet/ directory)"
    )
    parser.add_argument(
        "--json", action="store_true",
        help="emit the aggregated fleet summary as JSON",
    )
    parser.add_argument(
        "--straggler-k", type=float, default=3.5,
        help="leave-one-out MAD threshold (the sentinel's robust cut)",
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(args.log_dir):
        print(f"fleet_status: no such directory: {args.log_dir}",
              file=sys.stderr)
        return 2
    summary = aggregate_fleet(args.log_dir, straggler_k=args.straggler_k)
    summary["layouts"] = read_layout_notes(args.log_dir)
    summary["autoprof"] = autoprof_captures(args.log_dir)
    # Serve heartbeats (kind=serve) share the fleet/proc_*.jsonl files;
    # fold the per-replica serving view in when any process emitted them.
    serve = aggregate_serve(args.log_dir)
    if serve.get("replicas"):
        summary["serve"] = serve
    # Supervised runs (train.py --supervise, docs/elasticity.md): fold
    # the restart chain's headline into the fleet view — the heartbeat
    # streams this tool reads span ALL attempts, and a reader should
    # know they are looking at a chain, not one process lifetime.
    from sav_tpu.train.supervisor import load_chain  # stdlib-only module

    chain_doc = load_chain(args.log_dir)
    if chain_doc is not None:
        chain = (chain_doc.get("notes") or {}).get("chain") or {}
        summary["supervisor"] = {
            "outcome": chain_doc.get("outcome"),
            "attempts": len(chain.get("attempts") or []),
            "restart_reasons": [
                a.get("restart_reason")
                for a in (chain.get("attempts") or [])
                if a.get("restart_reason")
            ],
            "goodput": chain.get("goodput"),
            "skipped_steps": chain.get("skipped_steps"),
        }
    if args.json:
        print(json.dumps(summary, indent=2, default=str))
    else:
        render(args.log_dir, summary, sys.stdout)
        sup = summary.get("supervisor")
        if sup is not None:
            gp = sup.get("goodput") or {}
            print(
                f"Supervisor chain: {sup['attempts']} attempt(s), outcome "
                f"{sup['outcome']}, restarts {sup['restart_reasons']}, "
                f"goodput {gp.get('goodput_frac', 0.0):.1%} "
                f"(render with tools/run_report.py --chain)"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
