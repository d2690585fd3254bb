"""Regression sentinel (ISSUE 4): the tier-1 smoke over the checked-in
fixture histories pins the CI exit-code contract — 0 on a clean history,
1 on the planted throughput/MFU regression, 0 when the only deltas are
infra failures, 2 on usage/IO errors — plus unit coverage of the
median+MAD math and the record normalization it stands on."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from sav_tpu.obs.manifest import load_run_history, normalize_run_record

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "sentinel_fixtures")
SENTINEL = os.path.join(ROOT, "tools", "regression_sentinel.py")


def _load_sentinel():
    spec = importlib.util.spec_from_file_location("regression_sentinel", SENTINEL)
    module = importlib.util.module_from_spec(spec)
    # Registered BEFORE exec: dataclasses resolves the module's postponed
    # annotations through sys.modules.
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


sentinel = _load_sentinel()


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, SENTINEL, *args],
        capture_output=True, text=True, cwd=ROOT,
    )


# ------------------------------------------------------ exit-code contract


def test_clean_history_exits_zero():
    proc = _run_cli(os.path.join(FIXTURES, "clean"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "REGRESS" not in proc.stdout


def test_planted_regression_exits_one_and_names_the_metrics():
    proc = _run_cli(os.path.join(FIXTURES, "regressed"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    # The planted drop hits throughput AND mfu; input_wait stays clean.
    assert "REGRESS throughput" in proc.stdout
    assert "REGRESS mfu" in proc.stdout
    assert "REGRESS input_wait_frac" not in proc.stdout


def test_infra_failures_only_exits_zero_but_lists_them():
    """An unreachable backend is not a regression. Records
    with rc != 0 / parsed: null are reported, never scored."""
    proc = _run_cli(os.path.join(FIXTURES, "infra_only"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "2 infra failures" in proc.stdout
    assert "backend_unreachable" in proc.stdout
    assert "REGRESS" not in proc.stdout


def test_nonfinite_outcome_is_listed_but_never_scored():
    """ISSUE 5: a diverged (NaN) run's throughput is not a measurement.
    The nonfinite fixture's latest record carries outcome: nonfinite (a
    bench that planted an incident bundle); the sentinel must list it as
    an infra-style failure and score only the healthy history — exit 0."""
    proc = _run_cli(os.path.join(FIXTURES, "nonfinite"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1 infra failures" in proc.stdout
    assert "nonfinite" in proc.stdout
    assert "REGRESS" not in proc.stdout
    # And the record normalizes with empty metrics (never averaged).
    path = os.path.join(FIXTURES, "nonfinite", "BENCH_r04.json")
    with open(path) as f:
        record = normalize_run_record(json.load(f), label="r04")
    assert record.outcome == "nonfinite"
    assert not record.ok
    assert record.metrics == {}


def test_usage_and_io_errors_exit_two(tmp_path):
    assert _run_cli().returncode == 2  # no inputs
    assert _run_cli("/no/such/file.json").returncode == 2
    assert _run_cli("--metric", "nope", os.path.join(FIXTURES, "clean")
                    ).returncode == 2
    torn = tmp_path / "BENCH_torn.json"
    torn.write_text('{"rc": 0, "parsed"')  # torn tail of a crashed write
    assert _run_cli(str(torn)).returncode == 2


def test_json_report_is_machine_readable():
    proc = _run_cli("--json", os.path.join(FIXTURES, "regressed"))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["regressed"] is True
    regressed = {v["metric"] for v in payload["verdicts"] if v["regressed"]}
    assert regressed == {"throughput", "mfu"}


def test_quant_history_scores_under_quant_names_and_stays_isolated():
    """ISSUE 17: serve_bench --quant-weights lines carry quant="int8"
    and score under the quant_* metric names — an int8-only history.
    The float serve line planted at the head of both fixtures must
    neither flag nor be flagged: the plain serve metrics are simply
    unscorable there (one measurement), proving the histories never
    mix."""
    proc = _run_cli(os.path.join(FIXTURES, "quant_clean"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ok      quant_p99_latency_ms" in proc.stdout
    assert "ok      quant_serve_throughput" in proc.stdout
    assert "REGRESS" not in proc.stdout
    proc = _run_cli("--json", os.path.join(FIXTURES, "quant_regressed"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    flagged = {v["metric"] for v in payload["verdicts"] if v["regressed"]}
    assert flagged == {
        "quant_p99_latency_ms", "quant_serve_throughput",
        "quant_slo_hit_frac",
    }
    # The bf16 metrics were never scored at all — the float record is
    # lone history, not baseline, on both fixtures.
    scored = {v["metric"] for v in payload["verdicts"]}
    assert "p99_latency_ms" not in scored
    assert "serve_throughput" not in scored


# --------------------------------------------------------- detection math


def test_mad_threshold_adapts_to_series_noise():
    noisy = [100.0, 120.0, 80.0, 110.0, 90.0]
    quiet = [100.0, 100.5, 99.5, 100.2, 99.8]
    _, _, t_noisy = sentinel.robust_threshold(noisy, k=3.5, rel_floor=0.0)
    _, _, t_quiet = sentinel.robust_threshold(quiet, k=3.5, rel_floor=0.0)
    assert t_noisy > t_quiet > 0


def test_rel_floor_prevents_zero_variance_flagging():
    flat = [100.0] * 5
    _, mad, threshold = sentinel.robust_threshold(flat, k=3.5, rel_floor=0.05)
    assert mad == 0.0
    assert threshold == pytest.approx(5.0)  # 5% of the median, not zero


def test_zero_median_fraction_baseline_does_not_flag_jitter():
    """A perfectly-overlapped history records input_wait_frac 0.0 (the
    ledger rounds fractions to 4 decimals); the relative floor is inert at
    median 0, so the absolute floor must absorb sub-point jitter."""
    def rec(wait_frac):
        return normalize_run_record({
            "value": 1000.0, "unit": "img/s/chip",
            "goodput": {"fractions": {"input_wait": wait_frac}},
        })

    records = [rec(0.0), rec(0.0), rec(0.0), rec(0.0002)]
    verdict = sentinel.judge_metric(
        records, "input_wait_frac", k=3.5, rel_floor=0.05, min_history=2
    )
    assert verdict is not None and not verdict.regressed
    # A real input-side regression (5% of wall blocked) still flags.
    bad = sentinel.judge_metric(
        records[:3] + [rec(0.05)], "input_wait_frac", k=3.5,
        rel_floor=0.05, min_history=2,
    )
    assert bad.regressed


def test_min_history_below_one_is_a_usage_error():
    proc = _run_cli(
        "--min-history", "0", os.path.join(FIXTURES, "clean")
    )
    assert proc.returncode == 2
    assert "min-history" in proc.stderr


def test_judge_metric_directionality():
    def rec(value, ok=True):
        return normalize_run_record(
            {"value": value, "unit": "img/s/chip",
             "goodput": {"fractions": {"input_wait": value / 1e4}}},
        )

    stable = [rec(1000.0), rec(1010.0), rec(990.0)]
    # Higher-is-better: a drop flags, a rise does not.
    drop = sentinel.judge_metric(
        stable + [rec(500.0)], "throughput", k=3.5, rel_floor=0.05,
        min_history=2,
    )
    rise = sentinel.judge_metric(
        stable + [rec(1500.0)], "throughput", k=3.5, rel_floor=0.05,
        min_history=2,
    )
    assert drop.regressed and not rise.regressed
    # Lower-is-better (input_wait_frac): the same records' rising wait flags.
    wait = sentinel.judge_metric(
        stable + [rec(1500.0)], "input_wait_frac", k=3.5, rel_floor=0.05,
        min_history=2,
    )
    assert wait.regressed


def test_attention_core_frac_gates_on_where_time_went():
    """ISSUE 8: traced benches carry the measured attention-core time
    share (bench --trace via obs/traceview.py); a rise flags even when
    throughput noise hides it, and untraced histories are simply not
    scored for it."""
    def rec(frac):
        return normalize_run_record({
            "value": 1000.0, "unit": "img/s/chip",
            "attention_core_frac": frac,
        })

    stable = [rec(0.30), rec(0.31), rec(0.29)]
    rise = sentinel.judge_metric(
        stable + [rec(0.55)], "attention_core_frac", k=3.5,
        rel_floor=0.05, min_history=2,
    )
    assert rise is not None and rise.regressed
    drop = sentinel.judge_metric(
        stable + [rec(0.20)], "attention_core_frac", k=3.5,
        rel_floor=0.05, min_history=2,
    )
    assert drop is not None and not drop.regressed
    # Records without the metric (untraced benches) never enter the
    # series — a mixed history with too few traced runs is unscorable,
    # not wrong.
    untraced = [
        normalize_run_record({"value": 1000.0, "unit": "img/s/chip"})
        for _ in range(4)
    ]
    assert sentinel.judge_metric(
        untraced + [rec(0.9)], "attention_core_frac", k=3.5,
        rel_floor=0.05, min_history=2,
    ) is None
    # And when the NEWEST measurement is untraced, the metric is not
    # scorable either: re-judging an older traced record as 'the
    # candidate' would re-flag a stale value on every later untraced
    # bench (the r8 battery runs traced benches before the headline).
    assert sentinel.judge_metric(
        stable + [rec(0.55)] + untraced[:1], "attention_core_frac",
        k=3.5, rel_floor=0.05, min_history=2,
    ) is None
    assert "attention_core_frac" in sentinel.METRICS


def test_insufficient_history_is_not_scored():
    records = [
        normalize_run_record({"value": 100.0, "unit": "img/s/chip"}),
        normalize_run_record({"value": 10.0, "unit": "img/s/chip"}),
    ]
    assert sentinel.judge_metric(
        records, "throughput", k=3.5, rel_floor=0.05, min_history=2
    ) is None


# ----------------------------------------------------- record normalization


def test_history_orders_by_wrapper_n_not_filename(tmp_path):
    # Filename order disagrees with the run order: 'a.json' is run 9.
    (tmp_path / "a.json").write_text(json.dumps(
        {"n": 9, "rc": 0, "tail": "", "parsed": {"value": 5.0, "unit": "x"}}
    ))
    (tmp_path / "b.json").write_text(json.dumps(
        {"n": 1, "rc": 0, "tail": "", "parsed": {"value": 100.0, "unit": "x"}}
    ))
    records = load_run_history([str(tmp_path / "a.json"), str(tmp_path / "b.json")])
    assert [r.metrics["throughput"] for r in records] == [100.0, 5.0]


def test_fixture_bench_history_loads_and_separates_infra():
    fixture = os.path.join(FIXTURES, "infra_only")
    paths = sorted(
        os.path.join(fixture, name) for name in os.listdir(fixture)
        if name.startswith("BENCH_r")
    )
    records = load_run_history(paths)
    outcomes = [r.outcome for r in records]
    assert outcomes[0] == "ok"
    assert "backend_unreachable" in outcomes  # an rc=3 device-check abort
    assert any(not r.ok for r in records)
