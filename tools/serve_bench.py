#!/usr/bin/env python
"""Serving benchmark — open-loop load against the AOT serving engine.

The serving twin of ``bench.py``: spins up a :class:`ServeEngine`,
offers a synthetic open-loop request stream (arrivals on a fixed
schedule — the load does NOT slow down when the server does, which is
what makes p99 honest), and prints ONE parseable JSON line with the
serving headline: p50/p95/p99 latency, throughput, bucket occupancy,
padding-waste fraction, queue depth, deadline overruns, and the AOT
startup report (compile seconds + persistent-cache hit counts — the
warm-restart proof). A :class:`RunManifest` (kind ``serve``) is
finalized with the same numbers, so ``tools/regression_sentinel.py``
gates ``p99_latency_ms`` (lower-better) and ``serve_throughput``
(higher-better) exactly like training throughput (docs/serving.md).

A/B arms:
  --batch-1          ladder [1] — the no-batching baseline the dynamic
                     batcher must beat (docs/serving.md's throughput
                     proof; also pinned in tests/test_serve.py)
  --rate 0           flood (all requests offered at t=0): measures the
                     drain ceiling
  --rate R           Poisson-free fixed schedule at R req/s: measures
                     latency under a target load
  --quant-weights    int8 serving weights (per-channel scales,
                     docs/quantization.md): the HBM-density arm — the
                     line carries ``quant: "int8"`` and the sentinel
                     scores it under ``quant_p99_latency_ms`` /
                     ``quant_serve_throughput``, an int8-only history
                     that never contaminates the bf16 baseline

Fleet mode (``--replicas N`` — docs/serving.md "Fleet"): spins up N
supervised engine replicas (tools/serve_fleet.py under the PR-9
supervisor, shared log dir + compile cache) behind the wait-aware
:class:`~sav_tpu.serve.router.Router`, drives the SAME open-loop load
through the router, and emits one **fleet** JSON line —
``fleet_p99_latency_ms`` (lower-better) / ``fleet_throughput``
(higher-better) / ``fleet_shed`` — that the regression sentinel gates
exactly like the single-engine metrics. The chaos arm rides here:
``--chaos-kill-rank R`` SIGKILLs that replica mid-load (after
``--chaos-kill-at-frac`` of the requests have been offered), then the
line must show bounded fleet p99 (rerouted, no cliff), exact
accounting (completed + shed == offered, nothing silently lost), the
supervisor's warm restart (``compiled_from_scratch == 0``), and the
router folding the victim back in (the post-restart probe counts).
``--inject-delay RANK:SECONDS`` slows one replica per batch — the
straggler shape the router must shift load away from. The bench parent
NEVER imports jax in fleet mode (replicas own the backend).

Usage:
  python tools/serve_bench.py --model vit_ti_patch16 --requests 512
  python tools/serve_bench.py --checkpoint runs/train/ckpt --rate 200
  python tools/serve_bench.py --replicas 2 --requests 512 --rate 100
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _parse_buckets(text):
    return [int(b) for b in text.split(",") if b.strip()]


def run(args, manifest) -> dict:
    import numpy as np

    from sav_tpu.serve.batcher import QueueFullError
    from sav_tpu.serve.engine import ServeConfig, ServeEngine

    buckets = _parse_buckets(args.buckets) if args.buckets else None
    if args.batch_1:
        buckets = [1]
    config = ServeConfig(
        model_name=args.model,
        num_classes=args.num_classes,
        image_size=args.image_size,
        attention_backend=None if args.backend == "auto" else args.backend,
        attention_tune_cache=args.attn_tune_cache,
        model_overrides=(
            json.loads(args.model_overrides) if args.model_overrides else None
        ),
        buckets=buckets,
        max_batch=args.max_batch,
        max_queue=args.max_queue,
        deadline_ms=args.deadline_ms,
        checkpoint_dir=args.checkpoint,
        quant_weights=args.quant_weights,
        layout_preset=args.layout_preset,
        compilation_cache_dir=args.compilation_cache_dir,
        # Telemetry artifacts (serve heartbeats, slow-request exemplars,
        # anomaly captures) land next to the manifest; --no-telemetry is
        # the A/B arm the <2% overhead proof measures against.
        log_dir=args.log_dir,
        telemetry=not args.no_telemetry,
        heartbeat_secs=args.heartbeat_secs,
        slo_target=args.slo_target,
        probe_every_s=args.probe_every,
    )
    engine = ServeEngine(config, manifest=manifest)
    rng = np.random.default_rng(0)
    # A small pool of distinct request images (a fresh image per request
    # would spend the bench generating noise, one shared image would let
    # a cache cheat): submissions cycle the pool.
    pool = [
        rng.integers(
            0, 256, (args.image_size, args.image_size, 3), dtype=np.uint8
        )
        for _ in range(min(args.requests, 16))
    ]
    futures = []
    rejected = 0
    with engine:
        t0 = time.monotonic()
        for i in range(args.requests):
            if args.rate > 0:
                # Open loop: arrival i is DUE at i/rate regardless of how
                # the server is keeping up.
                due = t0 + i / args.rate
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            try:
                futures.append(engine.submit(pool[i % len(pool)]))
            except QueueFullError:
                rejected += 1
        deadline = time.monotonic() + args.drain_timeout
        logits = [
            future.result(timeout=max(deadline - time.monotonic(), 0.1))
            for future in futures
        ]
    summary = engine.stop()
    stats = engine.stats()
    return {
        "summary": summary,
        "startup": engine.startup_report,
        "offered": args.requests,
        "rejected_at_submit": rejected,
        # What was answered, not only how fast: a served checkpoint whose
        # logits are non-finite or all zero (a fresh zero-initialised
        # head) is a wrong answer with a fine latency.
        "logits_finite": bool(all(np.all(np.isfinite(x)) for x in logits)),
        "logits_absmax": float(max((np.abs(x).max() for x in logits),
                                   default=0.0)),
        "slo": stats.get("slo"),
        "telemetry": stats.get("telemetry"),
        "quality": stats.get("quality"),
    }


def _parse_inject_delay(spec):
    """``"1:0.4"`` -> (rank 1, 0.4s per-batch injected delay)."""
    if not spec:
        return None, 0.0
    rank, _, secs = str(spec).partition(":")
    try:
        return int(rank), float(secs)
    except ValueError:
        raise ValueError(
            f"--inject-delay wants RANK:SECONDS, got {spec!r}"
        ) from None


def _parse_noise_weights(spec):
    """``"1:0.3"`` -> (rank 1, 0.3 relative weight-noise scale)."""
    if not spec:
        return None, 0.0
    rank, _, scale = str(spec).partition(":")
    try:
        return int(rank), float(scale)
    except ValueError:
        raise ValueError(
            f"--noise-weights wants RANK:SCALE, got {spec!r}"
        ) from None


def run_fleet(args, manifest) -> dict:
    """Fleet mode: pool + router + open-loop load + (optional) chaos.

    The bench parent stays jax-free — replicas own the backend; every
    number here is host wall-clock accounting at the router.
    """
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serve_fleet as fleet_cli

    from sav_tpu.serve.batcher import QueueFullError, ServeClosedError
    from sav_tpu.serve.fleet import TcpTransport, read_endpoints
    from sav_tpu.serve.router import Router
    from sav_tpu.serve.telemetry import router_views

    log_dir = args.log_dir
    # SAV_LOCKWATCH=1 arms the runtime lock sanitizer around the whole
    # fleet run: every lock the router/transport/telemetry stack
    # constructs in THIS process is tracked, and the observed
    # acquisition-order graph lands in log_dir/lockwatch.json for the
    # tier-1 inversion-free assertion (docs/concurrency.md).
    watch = None
    watch_ctx = None
    if os.environ.get("SAV_LOCKWATCH"):
        from sav_tpu.analysis.lockwatch import watch_modules

        watch, watch_ctx = watch_modules([
            "sav_tpu.serve.router",
            "sav_tpu.serve.fleet",
            "sav_tpu.serve.telemetry",
            "sav_tpu.serve.batcher",
            "sav_tpu.serve.latency",
            "sav_tpu.obs.fleet",
        ])
        watch_ctx.__enter__()
    delay_rank, delay_s = _parse_inject_delay(args.inject_delay)
    noise_rank, noise_scale = _parse_noise_weights(args.noise_weights)
    env_fn = None
    if (delay_rank is not None and delay_s > 0) or (
            noise_rank is not None and noise_scale > 0):
        def env_fn(rank):
            env = {}
            if rank == delay_rank and delay_s > 0:
                env["SAV_CHAOS_SERVE_DELAY_S"] = str(delay_s)
            # The planted-corruption arm: this replica loads its
            # weights, then perturbs every float leaf BEFORE any
            # quantization — the shadow agreement gate must catch it.
            if rank == noise_rank and noise_scale > 0:
                env["SAV_CHAOS_NOISE_WEIGHTS"] = str(noise_scale)
            return env
    pool = fleet_cli.build_pool(args, log_dir, env_fn=env_fn)
    pool.start()
    transport = TcpTransport(log_dir)
    router = None
    try:
        ready = pool.wait_ready(
            args.replica_startup_timeout, transport=transport
        )
        platform = next(
            (d.get("platform") for d in ready.values() if d.get("platform")),
            None,
        )
        # Seed the router's step estimate from the replicas' measured
        # warmups (the batcher's own seed, read over the wire).
        step_seed = 0.05
        for doc in ready.values():
            warm = ((doc.get("startup") or {}).get("warmup_step_s")) or {}
            steps = [v for v in warm.values() if isinstance(v, (int, float))]
            if steps:
                step_seed = max(steps)
                break
        deadline_s = args.deadline_ms / 1e3
        router = Router(
            transport,
            views_fn=lambda: router_views(log_dir),
            max_batch=args.max_batch,
            default_step_s=step_seed,
            default_deadline_s=deadline_s,
            max_inflight=args.max_queue,
            refresh_secs=args.router_refresh_secs,
            ranks=range(args.replicas),
            workers=args.fleet_workers,
            log_dir=log_dir,
            heartbeat_secs=args.heartbeat_secs,
            shadow_rank=args.shadow_rank,
            shadow_frac=args.shadow_frac,
        )
        rng = np.random.default_rng(0)
        payloads = [
            rng.integers(
                0, 256, (args.image_size, args.image_size, 3),
                dtype=np.uint8,
            ).tobytes()
            for _ in range(min(args.requests, 16) or 1)
        ]
        chaos = None
        if args.chaos_kill_rank is not None:
            chaos = {
                "rank": args.chaos_kill_rank,
                "kill_at_request": max(
                    int(args.requests * args.chaos_kill_at_frac), 1
                ),
            }
        futures = []
        admit_rejects = 0
        t0 = time.monotonic()
        for i in range(args.requests):
            if args.rate > 0:
                due = t0 + i / args.rate
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            if chaos and i == chaos["kill_at_request"]:
                pid = pool.kill(chaos["rank"])
                chaos["killed_pid"] = pid
                chaos["kill_unix"] = round(time.time(), 3)
            try:
                futures.append(router.admit(
                    payloads[i % len(payloads)], deadline_s=deadline_s
                ))
            except QueueFullError:
                admit_rejects += 1  # router books shed_admit/rejected
        drain_deadline = time.monotonic() + args.drain_timeout
        counts = {"completed": 0, "shed": 0, "closed": 0, "errors": 0}
        for future in futures:
            try:
                future.result(
                    timeout=max(drain_deadline - time.monotonic(), 0.1)
                )
                counts["completed"] += 1
            except ServeClosedError:
                counts["closed"] += 1
            except QueueFullError:  # RouterShedError subclasses it
                counts["shed"] += 1
            except Exception:  # noqa: BLE001 — app error or stuck future
                counts["errors"] += 1
        # Fleet headline SNAPSHOT before any probe traffic: the probe
        # burst is fold-back proof, not measurement — its latencies and
        # sheds must not contaminate the scored fleet numbers.
        summary = router.summary()
        # ---- chaos: wait for the supervisor to bring the victim back
        # (new pid, warm cache), then prove the router folds it in.
        probe_routed = None
        if chaos and chaos.get("killed_pid"):
            victim = chaos["rank"]
            rec_deadline = time.monotonic() + args.chaos_recovery_timeout
            while time.monotonic() < rec_deadline:
                doc = read_endpoints(log_dir).get(victim)
                if (
                    doc is not None
                    and doc.get("pid") != chaos["killed_pid"]
                ):
                    try:
                        transport.invalidate(victim)
                        ping = transport.ping(victim)
                        chaos["restored_unix"] = round(time.time(), 3)
                        chaos["outage_s"] = round(
                            chaos["restored_unix"] - chaos["kill_unix"], 3
                        )
                        chaos["restart_startup"] = ping.get("startup")
                        break
                    except Exception:  # noqa: BLE001 — still warming
                        pass
                time.sleep(0.25)
            # Fold-back proof: once the victim heartbeats again the
            # router resumes routing to it — flood a probe burst and
            # count where it lands.
            if chaos.get("restored_unix") and args.probe_requests > 0:
                active_deadline = time.monotonic() + max(
                    args.heartbeat_secs * 20, 10.0
                )
                while time.monotonic() < active_deadline:
                    router.refresh()
                    state = router.stats()["replicas"].get(str(victim), {})
                    if state.get("state") == "active":
                        break
                    time.sleep(0.2)
                base = {
                    rank: v["routed"]
                    for rank, v in router.stats()["replicas"].items()
                }
                probe_futs = []
                # Probe deadline: generous enough to absorb a cold
                # replica, short enough that a lone probe's batcher
                # trickle wait (it ships at deadline - est) cannot
                # stall the bench for the full serving deadline.
                probe_deadline_s = max(min(deadline_s, 2.0), 1.0)
                for i in range(args.probe_requests):
                    try:
                        probe_futs.append(router.admit(
                            payloads[i % len(payloads)],
                            deadline_s=probe_deadline_s,
                        ))
                    except QueueFullError:
                        pass
                for future in probe_futs:
                    try:
                        future.result(timeout=30.0)
                    except Exception:  # noqa: BLE001 — probe only
                        pass
                probe_routed = {
                    rank: v["routed"] - base.get(rank, 0)
                    for rank, v in router.stats()["replicas"].items()
                }
    finally:
        if router is not None:
            router.close()
        pool.stop()
        if watch is not None:
            watch_ctx.__exit__(None, None, None)
            watch.write(os.path.join(log_dir, "lockwatch.json"))
    status = pool.status()
    # Distributed tracing (ISSUE 16): with the router's span ring and
    # the replicas' exports both on disk, run the offline clock-aligned
    # merge NOW so the line/manifest carry pointers to every artifact
    # (router export + per-replica exports + ONE merged fleet trace)
    # and the slowest cross-process walks land as fleet exemplars.
    from sav_tpu.obs.traceview import write_fleet_exemplars, write_fleet_trace

    traces_dir = os.path.join(log_dir, "serve_traces")
    router_export = os.path.join(
        traces_dir, "requests_router.trace.json.gz"
    )
    serve_traces = {
        "router": (
            router_export if os.path.isfile(router_export) else None
        ),
        "replicas": sorted(
            glob.glob(
                os.path.join(traces_dir, "requests_proc*.trace.json.gz")
            )
        ),
        "merged": write_fleet_trace(log_dir),
        "fleet_exemplars": len(write_fleet_exemplars(log_dir)),
    }
    endpoints = read_endpoints(log_dir)
    startup_warm = {
        str(rank): ((doc.get("startup") or {}).get("compiled_from_scratch"))
        for rank, doc in sorted(endpoints.items())
    }
    # Fleet metrics pipeline (ISSUE 19): the router's heartbeat thread
    # rolled the streams in-run; one final roll + flush here folds the
    # tail beats (router is stopped — single-writer cursor is free), so
    # the capacity/headroom fold and the ops console read the whole run
    # from rollups alone.
    from sav_tpu.obs.alerts import episodes as alert_episodes
    from sav_tpu.obs.alerts import read_alerts
    from sav_tpu.obs.rollup import Roller
    from sav_tpu.serve.telemetry import aggregate_serve

    try:
        roller = Roller(log_dir)
        roller.roll_once()
        roller.flush()
    except Exception:  # noqa: BLE001 — rollups are best-effort
        pass
    fleet_fold = (aggregate_serve(log_dir) or {}).get("fleet") or {}
    alert_eps = alert_episodes(read_alerts(log_dir))
    latency = summary.get("latency_ms") or {}
    # Client-side ledger: every offered request resolved as exactly one
    # of completed / shed (admission reject OR deadline shed on the
    # future) / closed / errors. A silently-lost request would surface
    # as a stuck future -> TimeoutError -> errors, so lost == 0 AND
    # errors == 0 together are the chaos criterion's accounting proof.
    shed_total = counts["shed"] + admit_rejects
    offered = args.requests
    accounting = {
        "offered": offered,
        "completed": counts["completed"],
        "shed": shed_total,
        "shed_at_admit": admit_rejects,
        "closed": counts["closed"],
        "errors": counts["errors"],
        "lost": (
            offered - counts["completed"] - shed_total
            - counts["closed"] - counts["errors"]
        ),
    }
    load_desc = f"{args.rate} req/s" if args.rate > 0 else "flood"
    # Outcome honesty (the PR-10 engine __exit__ contract, fleet-wide):
    # a run with replica app errors or stuck futures must NOT finalize
    # ok — its partial-run p99 (computed only over the requests that
    # happened to complete) would poison the sentinel's fleet baseline
    # forever. Honest sheds are fine; errors are not.
    outcome = (
        "ok"
        if counts["errors"] == 0 and accounting["lost"] == 0
        else "error"
    )
    out = {
        "metric": (
            f"{args.model} fleet p99 ms ({args.replicas} replicas, "
            f"{load_desc}, deadline {args.deadline_ms} ms, "
            f"{args.requests} reqs)"
        ),
        "unit": "ms",
        "outcome": outcome,
        "platform": platform,
        "replicas": args.replicas,
        "fleet_p50_latency_ms": latency.get("p50"),
        "fleet_p95_latency_ms": latency.get("p95"),
        "fleet_p99_latency_ms": latency.get("p99"),
        "fleet_throughput": summary.get("throughput_rps"),
        "fleet_capacity_rps": fleet_fold.get("capacity_rps"),
        "fleet_headroom_frac": fleet_fold.get("headroom_frac"),
        "fleet_shed": shed_total,
        "accounting": accounting,
        "rerouted": summary["rerouted"],
        "transport_failures": summary["transport_failures"],
        "router_overhead_ms": summary.get("router_overhead_ms"),
        "restarts": status["restarts"],
        "startup_warm": startup_warm,
        "router": summary,
        "serve_traces": serve_traces,
        "manifest": manifest.path,
        "log_dir": log_dir,
    }
    if chaos:
        out["chaos"] = chaos
    if probe_routed is not None:
        out["probe_routed"] = probe_routed
    # Prediction-quality headline (docs/quality.md): shadow agreement
    # from the router's own summary, probe health from the heartbeat
    # fold. Both skip-not-zero-fill — a run without a shadow rank or
    # probes must not read as "agreement 0". The shadow block is
    # re-read POST-close: the scored-fleet summary above is snapshotted
    # before close() on purpose (probe traffic must not contaminate the
    # latency numbers), but the shadow worker finishes draining its
    # mirror queue inside close() — the pre-close block would undercount
    # every sample still queued at drain time.
    shadow = (router.summary().get("shadow") if router is not None else None) \
        or summary.get("shadow") or {}
    if shadow:
        summary["shadow"] = shadow
    if isinstance(shadow.get("agreement"), (int, float)):
        out["quality_agreement"] = shadow["agreement"]
    if isinstance(fleet_fold.get("probe_ok_frac"), (int, float)):
        out["probe_ok_frac"] = fleet_fold["probe_ok_frac"]
    metrics = {
        "fleet/replicas": float(args.replicas),
        "fleet/restarts": float(status["restarts"]),
        "fleet/shed": float(shed_total),
        "fleet/rerouted": float(summary["rerouted"]),
    }
    # Zero-request honesty: latency/throughput absent, not zero-filled
    # (the sentinel skips records without them — the slo_hit_frac
    # contract).
    if isinstance(latency.get("p99"), (int, float)):
        metrics["fleet/p99_latency_ms"] = float(latency["p99"])
    if isinstance(summary.get("throughput_rps"), (int, float)):
        metrics["fleet/throughput_rps"] = float(summary["throughput_rps"])
    if isinstance(summary.get("router_overhead_ms"), (int, float)):
        metrics["fleet/router_overhead_ms"] = float(
            summary["router_overhead_ms"]
        )
    # Headroom is skip-not-zero-fill too: absent capacity stamps (old
    # replicas, zero-request runs) must not read as "no headroom".
    if isinstance(fleet_fold.get("headroom_frac"), (int, float)):
        metrics["fleet/headroom_frac"] = float(fleet_fold["headroom_frac"])
    if isinstance(shadow.get("agreement"), (int, float)):
        metrics["fleet/quality_agreement"] = float(shadow["agreement"])
    if isinstance(fleet_fold.get("probe_ok_frac"), (int, float)):
        metrics["fleet/probe_ok_frac"] = float(fleet_fold["probe_ok_frac"])
    manifest.note("metric", out["metric"])
    if platform:
        manifest.note("platform", platform)
    manifest.note("fleet", {
        "pool": status,
        "accounting": accounting,
        "chaos": chaos,
        "probe_routed": probe_routed,
        "capacity_rps": fleet_fold.get("capacity_rps"),
        "projected_rps": fleet_fold.get("projected_rps"),
        "headroom_frac": fleet_fold.get("headroom_frac"),
    })
    if shadow or isinstance(fleet_fold.get("probe_ok_frac"), (int, float)):
        manifest.note("quality", {
            "shadow": shadow or None,
            "probe_ok_frac": fleet_fold.get("probe_ok_frac"),
        })
    if alert_eps:
        out["alerts"] = alert_eps
        manifest.note("alerts", alert_eps)
    manifest.note("serve_traces", serve_traces)
    manifest.finalize(
        outcome,
        error=(
            None if outcome == "ok"
            else f"{counts['errors']} request error(s), "
            f"{accounting['lost']} unaccounted — partial-run fleet "
            "numbers must not enter the sentinel baseline"
        ),
        metrics=metrics,
    )
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--model", default="deit_s_patch16")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument(
        "--backend", default="auto",
        choices=["auto", "xla", "fused", "pallas"],
        help="attention backend (auto = the measured three-way dispatch; "
        "attn_tune cache winners apply at serving shapes too)",
    )
    parser.add_argument("--model-overrides", default=None, metavar="JSON")
    parser.add_argument(
        "--buckets", default=None,
        help="comma-separated batch-size ladder (default: powers of two "
        "up to --max-batch); one AOT executable per rung",
    )
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument(
        "--batch-1", action="store_true",
        help="ladder [1]: the no-batching A/B baseline",
    )
    parser.add_argument(
        "--layout-preset", default=None,
        help="declarative sharding layout (built-in name or a "
        "tools/mesh_tune.py preset path): the engine builds its mesh "
        "from it and SHARDS the serving params by its specs — one big "
        "model spans chips via TP (docs/parallelism.md)",
    )
    parser.add_argument("--max-queue", type=int, default=256)
    parser.add_argument("--deadline-ms", type=float, default=100.0)
    parser.add_argument(
        "--requests", type=int, default=512,
        help="total synthetic requests to offer",
    )
    parser.add_argument(
        "--rate", type=float, default=0.0,
        help="open-loop offered load in req/s (0 = flood everything at "
        "t=0, measuring the drain ceiling)",
    )
    parser.add_argument(
        "--drain-timeout", type=float, default=120.0,
        help="seconds to wait for the last future before giving up",
    )
    parser.add_argument(
        "--checkpoint", default=None,
        help="training checkpoint dir to serve (params-only restore — "
        "opt_state is never materialized)",
    )
    parser.add_argument(
        "--quant-weights", action="store_true",
        help="serve int8 weights (per-channel scales, "
        "sav_tpu/ops/quant.py): the float params are quantized at load "
        "and every projection/FFN dot runs int8×int8→int32 — the HBM-"
        "density A/B arm (docs/quantization.md). The line carries "
        "quant='int8' and the sentinel scores it under the quant_* "
        "metric names, so the int8 history never contaminates the "
        "bf16 baseline",
    )
    parser.add_argument("--compilation-cache-dir", default=None)
    parser.add_argument("--attn-tune-cache", default=None)
    parser.add_argument(
        "--log-dir", default=None,
        help="serve telemetry sink (heartbeats, slow-request exemplars, "
        "anomaly captures; default: the manifest's directory)",
    )
    parser.add_argument(
        "--no-telemetry", action="store_true",
        help="disable serve telemetry (spans/windows/heartbeats/SLO) — "
        "the overhead A/B arm (docs/serving.md)",
    )
    parser.add_argument(
        "--heartbeat-secs", type=float, default=5.0,
        help="serve heartbeat cadence (kind=serve lines in "
        "fleet/proc_<i>.jsonl; 0 disables)",
    )
    parser.add_argument(
        "--slo-target", type=float, default=0.99,
        help="deadline-hit-rate SLO objective (burn rates are measured "
        "against the 1-target error budget)",
    )
    parser.add_argument(
        "--replicas", type=int, default=0,
        help="fleet mode: N supervised engine replicas behind the "
        "wait-aware router (0 = the single in-process engine); emits "
        "the fleet_* metrics line (docs/serving.md 'Fleet')",
    )
    parser.add_argument(
        "--inject-delay", default=None, metavar="RANK:SECONDS",
        help="fleet mode: slow one replica by SECONDS per batch (the "
        "straggler arm — the router must shift load away from it)",
    )
    parser.add_argument(
        "--shadow-rank", type=int, default=None,
        help="fleet mode: mirror a sampled fraction of completed live "
        "requests to this replica and score top-1/logit agreement — "
        "report-only, off the latency path; the shadow rank never "
        "serves routed traffic (docs/quality.md)",
    )
    parser.add_argument(
        "--shadow-frac", type=float, default=0.05,
        help="fraction of admitted requests mirrored to the shadow rank",
    )
    parser.add_argument(
        "--probe-every", type=float, default=0.0,
        help="seconds between golden-probe runs on each replica "
        "(0 disables): the checked-in probe batch's logit fingerprint "
        "proves weight integrity across restarts (docs/quality.md)",
    )
    parser.add_argument(
        "--noise-weights", default=None, metavar="RANK:SCALE",
        help="fleet chaos arm: perturb one replica's float weights at "
        "load by SCALE*std relative noise — the planted corruption the "
        "shadow agreement gate must catch",
    )
    parser.add_argument(
        "--chaos-kill-rank", type=int, default=None,
        help="fleet mode chaos arm: SIGKILL this replica mid-load; the "
        "line then carries the outage, the warm-restart proof, and the "
        "fold-back probe counts",
    )
    parser.add_argument(
        "--chaos-kill-at-frac", type=float, default=0.4,
        help="kill after this fraction of the requests has been offered",
    )
    parser.add_argument(
        "--chaos-recovery-timeout", type=float, default=180.0,
        help="seconds to wait for the supervisor to restart the victim",
    )
    parser.add_argument(
        "--probe-requests", type=int, default=16,
        help="fold-back probe burst after a chaos recovery (0 disables)",
    )
    parser.add_argument(
        "--fleet-workers", type=int, default=16,
        help="router dispatch worker threads (fleet mode)",
    )
    parser.add_argument(
        "--router-refresh-secs", type=float, default=0.5,
        help="router heartbeat-view refresh cadence (fleet mode)",
    )
    parser.add_argument(
        "--replica-startup-timeout", type=float, default=600.0,
        help="seconds to wait for every replica endpoint + ping",
    )
    parser.add_argument(
        "--max-restarts", type=int, default=2,
        help="per-replica supervisor restart budget (fleet mode)",
    )
    parser.add_argument(
        "--restart-backoff", type=float, default=0.5,
        help="per-replica supervisor backoff base seconds (fleet mode)",
    )
    parser.add_argument(
        "--manifest", default=None,
        help="run-manifest path (default: a per-run "
        "runs/serve/manifest-serve-<stamp>.json — the sentinel's "
        "directory expansion globs manifest*.json)",
    )
    args = parser.parse_args(argv)
    if args.quant_weights and args.replicas:
        # The fleet replicas are their own processes with their own
        # engine configs (tools/serve_fleet.py) — wiring the quant arm
        # through the pool is future work, and silently serving bf16
        # under a quant-labelled line would poison the quant_* baseline.
        parser.error("--quant-weights is a single-engine A/B arm; it "
                     "does not compose with --replicas yet")
    if args.shadow_rank is not None:
        # A shadow needs one live rank to mirror FROM plus the shadow
        # itself; shadowing in single-engine mode has nothing to score.
        if args.replicas < 2:
            parser.error("--shadow-rank needs --replicas >= 2 (a live "
                         "rank plus the mirrored shadow)")
        if not 0 <= args.shadow_rank < args.replicas:
            parser.error("--shadow-rank must name one of the replica "
                         "ranks")
    if args.noise_weights and not args.replicas:
        parser.error("--noise-weights is a fleet chaos arm; it needs "
                     "--replicas")
    if args.manifest is None:
        stamp = f"{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}"
        args.manifest = (
            os.path.join("runs", "serve_fleet", f"manifest-fleet-{stamp}.json")
            if args.replicas
            else os.path.join("runs", "serve", f"manifest-serve-{stamp}.json")
        )
    if args.log_dir is None:
        args.log_dir = os.path.dirname(args.manifest) or "."

    from sav_tpu.obs.manifest import RunManifest, classify_exception

    manifest = RunManifest(
        args.manifest,
        kind="serve_fleet" if args.replicas else "serve",
        argv=sys.argv[1:],
    )
    manifest.begin()
    from sav_tpu.utils.device_check import (
        BackendUnreachableError,
        abort_unreachable,
        check_accelerator,
    )

    def _abort_backend_unreachable(error) -> int:
        # Exit 3 + outcome backend_unreachable + ONE parseable stdout
        # line (the bench.py contract).
        return abort_unreachable(
            "serve_bench", error, manifest,
            record={"metric": f"{args.model} serve"},
        )

    if not args.replicas:
        # Single engine: this process owns the chip, so it checks. In
        # fleet mode the parent never imports jax — each replica
        # process checks for itself (tools/serve_fleet.py).
        try:
            check_accelerator()
        except BackendUnreachableError as e:
            return _abort_backend_unreachable(e)

    try:
        if args.replicas:
            # Fleet mode finalizes its own manifest (kind serve_fleet)
            # and never imports jax in this parent process.
            out = run_fleet(args, manifest)
            print(json.dumps(out))
            return 0 if out.get("outcome") == "ok" else 1
        result = run(args, manifest)
    except BackendUnreachableError as e:
        # A replica found no chip of its own (ReplicaPool.wait_ready).
        return _abort_backend_unreachable(e)
    except BaseException as e:
        outcome = classify_exception(e)
        manifest.finalize(outcome, error=repr(e), exit_code=1)
        print(json.dumps({
            "outcome": outcome,
            "error": repr(e)[:500],
            "manifest": manifest.path,
        }))
        raise

    import jax

    summary = result["summary"]
    # A zero-request run (instantly-closed engine, everything shed) is
    # an honest measurement of "nothing was served": the latency keys
    # are null and slo_hit_frac is absent — never a traceback, and the
    # sentinel skips rather than zero-fills (docs/serving.md).
    latency = summary.get("latency_ms", {})
    ladder_desc = "bs1" if args.batch_1 else (
        args.buckets or f"pow2<={args.max_batch}"
    )
    load_desc = f"{args.rate} req/s" if args.rate > 0 else "flood"
    weights_desc = ", int8 weights" if args.quant_weights else ""
    out = {
        "metric": (
            f"{args.model} serve p99 ms (buckets {ladder_desc}, "
            f"{load_desc}, deadline {args.deadline_ms} ms, "
            f"{args.requests} reqs{weights_desc})"
        ),
        "unit": "ms",
        "outcome": "ok",
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "p50_latency_ms": latency.get("p50"),
        "p95_latency_ms": latency.get("p95"),
        "p99_latency_ms": latency.get("p99"),
        "serve_throughput": summary["throughput_rps"],
        "padding_waste_frac": summary["padding_waste_frac"],
        "bucket_occupancy": summary["bucket_occupancy"],
        "queue_depth_avg": summary["queue_depth_avg"],
        "queue_depth_max": summary["queue_depth_max"],
        "deadline_overruns": summary["deadline_overruns"],
        "requests": summary["requests"],
        "rejected": result["rejected_at_submit"],
        "logits_finite": result["logits_finite"],
        "logits_absmax": result["logits_absmax"],
        "startup": result["startup"],
        "manifest": manifest.path,
    }
    if args.quant_weights:
        # The quant stamp routes this line to the sentinel's quant_*
        # metric names (sav_tpu/obs/manifest.py _bench_line_metrics) —
        # int8 and bf16 latencies are different baselines and must
        # never share a history. Older (float) lines lack the key.
        out["quant"] = "int8"
    slo = result.get("slo") or {}
    if isinstance(slo.get("hit_frac"), (int, float)):
        out["slo_hit_frac"] = slo["hit_frac"]
        out["burn_rate"] = slo.get("burn_rate")
    quality = result.get("quality") or {}
    if isinstance(quality.get("probe_ok_frac"), (int, float)):
        # Probe health rides the line only when probes actually ran —
        # skip-not-zero-fill, same as slo_hit_frac.
        out["probe_ok_frac"] = quality["probe_ok_frac"]
    telemetry = result.get("telemetry")
    if telemetry is not None:
        out["telemetry"] = {
            "heartbeats": int(telemetry.get("heartbeats", 0)),
            "exemplars": int(telemetry.get("exemplars", 0)),
            "overhead_s": telemetry.get("overhead_s"),
            "log_dir": args.log_dir,
        }
    # Engine.stop() finalized the manifest with the serve/* metrics
    # (sav_tpu/obs/manifest.py reads serve/p99_latency_ms and
    # serve/throughput_rps back out as the sentinel's metric names);
    # ride the platform + metric description along.
    manifest.note("metric", out["metric"])
    manifest.note("platform", out["platform"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
