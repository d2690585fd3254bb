#!/usr/bin/env python
"""Perf regression sentinel over the run history (CI gate).

Ingests the bench/manifest record history — driver ``BENCH_r*.json``
wrappers, raw ``bench.py`` JSON lines, and ``RunManifest`` files
(``sav_tpu/obs/manifest.py`` normalizes all three) — separates **infra
failures** (``rc != 0``, ``parsed: null``, ``outcome:
backend_unreachable/hang/...``) from **measurements**, and flags
regressions in the latest measurement against the robust statistics of
the prior ones.

Detection is median + MAD (median absolute deviation), the standard
robust outlier test: for each tracked metric the newest measurement is a
regression when it falls on the wrong side of
``median ± max(K * 1.4826 * MAD, rel_floor * |median|)`` — the MAD term
adapts to the series' own noise (windows on a shared host vary,
docs/benchmarking.md Trap 3), the relative floor keeps a
zero-variance history from flagging sub-percent jitter.

Tracked metrics: ``throughput`` (img/s/chip, higher is better), ``mfu``
(higher), ``input_wait_frac`` (share of wall time blocked on input,
lower), ``attention_core_frac`` (measured attention-core share of
device time from ``bench.py --trace``, lower — present only on traced
benches; untraced records are skipped, not zero-filled),
``goodput_frac`` (elastic-training goodput from supervisor manifest
chains, higher — supervised runs only, docs/elasticity.md),
``p99_latency_ms`` (serving tail latency from ``tools/serve_bench.py``,
lower), ``serve_throughput`` (serving req/s, higher),
``slo_hit_frac`` (deadline-hit fraction from the r11 serve telemetry's
SLO tracker, higher — all present only on serving records,
docs/serving.md), ``fleet_p99_latency_ms`` /
``fleet_throughput`` (the r15 replica-fleet router's end-to-end tail
latency, lower, and fleet req/s, higher — present only on
``serve_bench --replicas`` records), and ``quant_p99_latency_ms`` /
``quant_serve_throughput`` / ``quant_slo_hit_frac`` (the int8
quantized-weights serving arm, ``serve_bench --quant-weights`` —
present only on records stamped ``quant: "int8"``, an int8-only
history isolated from the bf16 baseline; docs/quantization.md). Infra
failures
are *reported but never scored* — an unreachable backend is
not a regression, and a history whose only deltas
are infra failures exits clean.

Exit-code contract (CI keys on it, like savlint's):

  0 — no regression (infra failures, if any, are listed)
  1 — at least one metric regressed
  2 — usage or I/O error (missing file, unparseable JSON, unknown metric)

Usage:
  python tools/regression_sentinel.py BENCH_r*.json
  python tools/regression_sentinel.py .                # dir: BENCH_*.json
  python tools/regression_sentinel.py --json --metric throughput mfu -- *.json
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import statistics
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _REPO_ROOT)

from sav_tpu.obs.manifest import load_run_history  # noqa: E402

# Scale factor turning MAD into a stdev-consistent estimator (normal dist).
MAD_SCALE = 1.4826

#: metric name -> (larger is better, absolute deviation floor). The
#: absolute floor matters for fraction metrics whose healthy baseline is
#: exactly 0.0 (well-overlapped runs record input_wait_frac 0.0 after the
#: ledger's 4-decimal rounding): a zero median zeroes the *relative*
#: floor, and without an absolute one the first 0.0002 of jitter would
#: flag. 0.01 = one point of wall share.
METRICS = {
    "throughput": (True, 0.0),
    "mfu": (True, 0.0),
    "input_wait_frac": (False, 0.01),
    # Measured attention-core share of device time (bench --trace via
    # obs/traceview.py): lower is better — a rise means the step got
    # slower WHERE the fused-kernel work lives, even if throughput noise
    # hides it. Absolute floor: two points of step share, same rationale
    # as input_wait_frac's (a flat history must not flag jitter).
    "attention_core_frac": (False, 0.02),
    # Elastic-training goodput fraction (supervisor manifest chains,
    # docs/elasticity.md): 1 − (lost + restart-backoff)/wall. Higher is
    # better — a drop means preemptions started costing real wall time
    # (checkpoint cadence too coarse, restarts thrashing). Present only
    # on supervised runs; unsupervised records are skipped, not
    # zero-filled. Absolute floor: one point of wall share.
    "goodput_frac": (True, 0.01),
    # Serving tail latency (tools/serve_bench.py via the LatencyLedger;
    # docs/serving.md): lower is better — a rise means requests started
    # missing their budget even if throughput held. Present only on
    # serving records (serve manifests / serve_bench lines); training
    # records are skipped, not zero-filled — the attention_core_frac
    # contract. Absolute floor 1 ms: sub-millisecond jitter on a flat
    # history is scheduling noise, not a regression.
    "p99_latency_ms": (False, 1.0),
    # Serving request throughput (req/s over the serving window). Higher
    # is better. Same presence contract as p99_latency_ms.
    "serve_throughput": (True, 0.0),
    # Serving SLO hit fraction (share of requests that met their
    # deadline, incl. shed requests as misses — sav_tpu/serve/telemetry
    # SLOTracker via the serve manifest / serve_bench line;
    # docs/serving.md). Higher is better — a drop means the tail
    # started blowing budgets even if mean throughput held. Present
    # only on r11+ serving records; older serve records and training
    # records are skipped, not zero-filled (the attention_core_frac
    # contract). Absolute floor: one point of hit rate — a flat 1.0
    # history must not flag a single 0.997 blip.
    "slo_hit_frac": (True, 0.01),
    # Fleet serving tail latency (tools/serve_bench.py --replicas — the
    # router-observed p99 over N engine replicas; docs/serving.md
    # "Fleet"): lower is better. A SEPARATE metric from p99_latency_ms
    # on purpose: one replica's tail and the fleet's tail are different
    # SLOs with different baselines (the fleet's includes routing,
    # reroutes, and chaos), and mixing them would poison both
    # histories. Present only on fleet records (fleet bench lines /
    # kind=serve_fleet manifests); everything else is skipped, not
    # zero-filled. Absolute floor 1 ms, the p99_latency_ms rationale.
    "fleet_p99_latency_ms": (False, 1.0),
    # Fleet request throughput (router-completed req/s over the serving
    # span). Higher is better — a drop with stable per-replica
    # throughput means the ROUTER became the bottleneck (bad balancing,
    # over-shedding). Same presence contract as fleet_p99_latency_ms.
    "fleet_throughput": (True, 0.0),
    # Quantized-weights serving tail latency (serve_bench
    # --quant-weights — int8 weights with per-channel scales,
    # docs/quantization.md): lower is better. A SEPARATE metric from
    # p99_latency_ms on purpose, the fleet_* precedent: int8 and bf16
    # runs execute different programs with different HBM traffic, so
    # they are different baselines — a quant line sneaking into the
    # float history (or vice versa) would poison both. Present only on
    # records stamped ``quant: "int8"`` (lines) /
    # ``serve/quant_weights`` (manifests); float serving records and
    # everything else are skipped, not zero-filled. Absolute floor
    # 1 ms, the p99_latency_ms rationale.
    "quant_p99_latency_ms": (False, 1.0),
    # Quantized-weights request throughput (req/s). Higher is better —
    # a drop with a flat float baseline means the INT8 path regressed
    # (dequant epilogue, scale layout), not serving in general. Same
    # presence contract as quant_p99_latency_ms.
    "quant_serve_throughput": (True, 0.0),
    # Quantized-weights SLO hit fraction. Higher is better; one point
    # of hit rate floor, the slo_hit_frac rationale. Same presence
    # contract as quant_p99_latency_ms.
    "quant_slo_hit_frac": (True, 0.01),
    # Router tracing overhead per completed request (ms — the router's
    # self-accounted trace/stamp/window cost, ISSUE 16; the fleet twin
    # of the engine's serve_overhead accounting). Lower is better — a
    # rise means the observability layer itself started taxing the
    # routing hot path. Present only on traced fleet records; older
    # fleet records and everything else are skipped, not zero-filled.
    # Absolute floor 0.05 ms: the contract bounds the stamp cost near
    # 0.1 ms/request, so sub-50µs jitter on a flat history is
    # scheduler noise, not a regression.
    "router_overhead_ms": (False, 0.05),
    # Fleet headroom fraction ((capacity - projected load) / capacity,
    # ISSUE 19 — the capacity/headroom fold over the rollup ladder,
    # docs/fleet.md). Higher is better: a drop with flat latency means
    # measured capacity shrank (slower steps, a lost replica's stamps)
    # or projected load grew — the fleet is closer to saturation than
    # the tail metrics show yet. Present only on fleet records whose
    # replicas stamped capacity_rps; older records are skipped, not
    # zero-filled. Absolute floor 0.02 (two points of headroom):
    # projection noise on a flat history is not a regression.
    "fleet_headroom_frac": (True, 0.02),
    # Shadow agreement (ISSUE 20 — min over (primary_dtype,
    # shadow_dtype) pairs of the top-1 agreement rate between live
    # replies and their mirrored shadow-replica replies;
    # docs/quality.md). Higher is better: a drop means replicas stopped
    # agreeing on PREDICTIONS — weight corruption, a bad swap, or a
    # numerics regression that latency metrics cannot see. Present only
    # on fleet records with a shadow rank (serve_bench --shadow-rank);
    # everything else is skipped, not zero-filled — a run without a
    # shadow is not "zero agreement". Absolute floor: one point of
    # agreement, the slo_hit_frac rationale.
    "quality_agreement": (True, 0.01),
    # Golden-probe pass fraction (probe_ok / probe_runs — fleet records
    # fold min across replicas; docs/quality.md). Higher is better: a
    # drop means a replica's logit fingerprint stopped matching the
    # checked-in reference — wrong weights, silent corruption, or a
    # numerics change under a fixed executable. Present only on records
    # whose engines ran probes (--probe-every); probe-less runs are
    # skipped, not zero-filled. One point of pass rate floor.
    "probe_ok_frac": (True, 0.01),
}

EXIT_CLEAN, EXIT_REGRESSION, EXIT_USAGE = 0, 1, 2


@dataclasses.dataclass
class Verdict:
    metric: str
    regressed: bool
    candidate: float
    candidate_label: str
    median: float
    mad: float
    threshold: float
    baseline_n: int
    reason: str


def robust_threshold(
    values: list, k: float, rel_floor: float, abs_floor: float = 0.0
) -> tuple[float, float, float]:
    """(median, MAD, allowed deviation) of a baseline series."""
    med = statistics.median(values)
    mad = statistics.median(abs(v - med) for v in values)
    threshold = max(k * MAD_SCALE * mad, rel_floor * abs(med), abs_floor)
    return med, mad, threshold


def judge_metric(
    records, metric: str, *, k: float, rel_floor: float, min_history: int
):
    """Verdict for one metric over ordered records (None = not scorable)."""
    higher_better, abs_floor = METRICS[metric]
    ok_records = [r for r in records if r.ok]
    series = [
        (r, r.metrics[metric]) for r in ok_records if metric in r.metrics
    ]
    if len(series) < min_history + 1:
        return None
    if series[-1][0] is not ok_records[-1]:
        # The newest measurement does not carry this metric (e.g. an
        # untraced bench after traced ones — attention_core_frac is
        # optional): scoring would re-judge a STALE record as "the
        # candidate" and re-flag an old value forever. Not scorable.
        return None
    (candidate_rec, candidate) = series[-1]
    baseline = [v for _, v in series[:-1]]
    med, mad, threshold = robust_threshold(baseline, k, rel_floor, abs_floor)
    if higher_better:
        regressed = candidate < med - threshold
        direction = "below"
    else:
        regressed = candidate > med + threshold
        direction = "above"
    reason = (
        f"{candidate:.6g} is {direction} the baseline median {med:.6g} "
        f"by more than {threshold:.6g} (MAD {mad:.6g}, n={len(baseline)})"
        if regressed
        else f"within {threshold:.6g} of median {med:.6g} (n={len(baseline)})"
    )
    return Verdict(
        metric=metric, regressed=regressed, candidate=candidate,
        candidate_label=candidate_rec.label, median=med, mad=mad,
        threshold=threshold, baseline_n=len(baseline), reason=reason,
    )


def expand_inputs(paths: list) -> list:
    """Files stay files; a directory expands to its BENCH_*.json +
    manifest*.json records (bench writes per-run manifest-<stamp> files)."""
    out = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(glob.glob(os.path.join(p, "BENCH_*.json"))))
            out.extend(sorted(glob.glob(os.path.join(p, "manifest*.json"))))
        else:
            out.append(p)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "paths", nargs="*",
        help="record files (BENCH_r*.json / bench lines / manifests) or "
        "directories (expanded to their BENCH_*.json)",
    )
    parser.add_argument(
        "--metric", nargs="+", default=sorted(METRICS),
        help=f"metrics to score (subset of {sorted(METRICS)})",
    )
    parser.add_argument(
        "--threshold", type=float, default=3.5, metavar="K",
        help="flag when the candidate deviates more than K scaled MADs "
        "from the baseline median (3.5 is the conventional robust cut)",
    )
    parser.add_argument(
        "--rel-floor", type=float, default=0.05,
        help="minimum allowed deviation as a fraction of the median "
        "(keeps a zero-variance baseline from flagging noise)",
    )
    parser.add_argument(
        "--min-history", type=int, default=2,
        help="baseline measurements required before a metric is scored",
    )
    parser.add_argument(
        "--json", action="store_true", help="machine-readable report"
    )
    args = parser.parse_args(argv)

    if args.min_history < 1:
        # 0 would make the baseline empty (median of nothing) — a usage
        # error, not a crash and not a "regression found" exit 1.
        print(
            f"sentinel: --min-history must be >= 1, got {args.min_history}",
            file=sys.stderr,
        )
        return EXIT_USAGE
    for metric in args.metric:
        if metric not in METRICS:
            print(
                f"sentinel: unknown metric {metric!r} "
                f"(have {sorted(METRICS)})",
                file=sys.stderr,
            )
            return EXIT_USAGE
    paths = expand_inputs(args.paths)
    if not paths:
        print(
            "sentinel: no input records (pass files or a directory "
            "containing BENCH_*.json)",
            file=sys.stderr,
        )
        return EXIT_USAGE
    try:
        records = load_run_history(paths)
    except (OSError, ValueError) as e:
        print(f"sentinel: cannot read history: {e}", file=sys.stderr)
        return EXIT_USAGE

    infra = [r for r in records if not r.ok]
    measurements = [r for r in records if r.ok]
    verdicts = [
        v for v in (
            judge_metric(
                records, m, k=args.threshold, rel_floor=args.rel_floor,
                min_history=args.min_history,
            )
            for m in args.metric
        )
        if v is not None
    ]
    regressions = [v for v in verdicts if v.regressed]

    if args.json:
        print(json.dumps({
            "records": len(records),
            "measurements": len(measurements),
            "infra_failures": [
                {"label": r.label, "outcome": r.outcome, "detail": r.detail}
                for r in infra
            ],
            "verdicts": [dataclasses.asdict(v) for v in verdicts],
            "regressed": bool(regressions),
        }, indent=2))
    else:
        print(
            f"sentinel: {len(records)} records — {len(measurements)} "
            f"measurements, {len(infra)} infra failures"
        )
        for r in infra:
            print(f"  infra   {r.label}: {r.outcome} ({r.detail})")
        for v in verdicts:
            tag = "REGRESS" if v.regressed else "ok"
            print(
                f"  {tag:<7} {v.metric}: latest {v.candidate:.6g} "
                f"({v.candidate_label}) — {v.reason}"
            )
        if not verdicts:
            print(
                "  (no metric had enough measurement history to score; "
                f"need {args.min_history + 1} ok records)"
            )
    return EXIT_REGRESSION if regressions else EXIT_CLEAN


if __name__ == "__main__":
    sys.exit(main())
