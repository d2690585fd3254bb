"""Run manifests — one structured JSON record per run, finalized on every
exit path.

The motivating failure: a backend-unreachable bench once
recorded ``"rc": 3, "parsed": null`` plus a free-text stderr tail, so no
tool could tell "infra was down" from "the code regressed". The manifest
replaces that parse-a-text-tail status quo: ``train.py`` and ``bench.py``
write a :class:`RunManifest` at start (config, argv, environment
fingerprint) and finalize it with a machine-readable **outcome** on every
way out — success, exception, watchdog fire, backend-unreachable abort.

Outcome taxonomy (:data:`OUTCOMES`):

  ok                   — the run completed
  backend_unreachable  — no TPU at startup (utils.device_check, exit 3)
  hang                 — the hang watchdog fired (obs.watchdog exit 4)
  oom                  — device allocator exhaustion
  nonfinite            — the run died on NaN/Inf numerics
                         (``FloatingPointError`` from debug_nans,
                         checkify nan_checks). Like the other non-``ok``
                         outcomes it is never scored by the regression
                         sentinel — a diverged run's throughput is not a
                         measurement — and the flight recorder's incident
                         bundle (``notes.incidents``) makes it replayable
                         (sav_tpu.obs.recorder, tools/replay_step.py)
  error                — any other exception
  running              — transient: the run is (or died too hard to say)

Design rules: stdlib-only (the backend-unreachable path must run without
jax — importing it is exactly what hangs); every write is atomic
(tmp + ``os.replace``) so a watchdog ``os._exit`` mid-write cannot tear
the file; ``finalize`` is first-wins idempotent and thread-safe, so the
watchdog thread and a crashing main thread cannot double-report; and a
failed manifest write never takes the run down (telemetry must not).

The module also owns run-record *reading*: :func:`normalize_run_record` /
:func:`load_run_history` fold the three shapes history comes in (driver
``BENCH_r*.json`` wrappers, raw bench JSON lines, manifests) into one
:class:`RunRecord` view that separates infra failures from measurements —
shared by ``tools/regression_sentinel.py`` and ``tools/run_report.py``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform as _platform
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Optional

OUTCOMES = (
    "ok", "backend_unreachable", "hang", "oom", "nonfinite", "error",
)
MANIFEST_SCHEMA = 1


def classify_exception(exc: BaseException) -> str:
    """Map an exception to a manifest outcome.

    Matches on type names and message text (not imports) so this stays
    stdlib-only: NaN/Inf numerics deaths (``FloatingPointError`` from the
    trainer's debug_nans asserts,
    checkify's "nan generated by" errors, "non-finite" messages) →
    ``nonfinite``; allocator exhaustion (``RESOURCE_EXHAUSTED``,
    "out of memory", ``MemoryError``) → ``oom``; everything else →
    ``error``.
    """
    text = f"{type(exc).__name__}: {exc}".lower()
    if (
        isinstance(exc, FloatingPointError)
        or "non-finite" in text
        or "nan generated by" in text  # jax.experimental.checkify
    ):
        return "nonfinite"
    if (
        "resource_exhausted" in text
        or "out of memory" in text
        or isinstance(exc, MemoryError)
    ):
        return "oom"
    return "error"


def _git_sha() -> Optional[str]:
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=2.0,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except Exception:
        return None
    sha = proc.stdout.strip()
    return sha if proc.returncode == 0 and sha else None


def environment_fingerprint() -> dict:
    """Host/toolchain fingerprint, safe to call before (and without) jax.

    Deliberately does NOT import jax and does NOT touch ``jax.devices()``
    even when jax is already imported — the manifest is written before
    the device check, the unreachable-backend path is exactly where the
    fingerprint must still work, and a parent that must leave the chip
    to its children (supervisor, serve pool) writes manifests too.
    Callers that hold live devices add backend facts via
    :meth:`RunManifest.note`.
    """
    env = {
        "python": sys.version.split()[0],
        "platform": _platform.platform(),
        "hostname": socket.gethostname(),
        "argv0": sys.argv[0] if sys.argv else None,
        "git_sha": _git_sha(),
        "jax_platforms": os.environ.get("JAX_PLATFORMS") or None,
    }
    if "jax" in sys.modules:  # version only — never device init
        env["jax"] = getattr(sys.modules["jax"], "__version__", None)
    return env


class RunManifest:
    """Lifecycle: ``begin()`` writes an in-progress record; ``note()`` /
    ``set_metrics()`` accrete facts; ``finalize(outcome)`` stamps the one
    terminal outcome (first caller wins — later finalizes are ignored, so
    an exception handler racing the watchdog cannot overwrite ``hang``).
    """

    def __init__(
        self,
        path: str,
        *,
        kind: str,
        argv: Optional[list] = None,
        config: Optional[dict] = None,
        clock=time.time,
    ):
        self.path = path
        self._clock = clock
        self._lock = threading.Lock()
        self._enabled = True
        self._data: dict[str, Any] = {
            "schema": MANIFEST_SCHEMA,
            "kind": kind,
            "outcome": "running",
            "argv": list(argv) if argv is not None else None,
            "config": config,
            "env": environment_fingerprint(),
            "created_unix": round(float(clock()), 3),
            "finalized_unix": None,
            "exit_code": None,
            "error": None,
            "notes": {},
            "metrics": {},
        }

    # ------------------------------------------------------------ lifecycle

    @property
    def outcome(self) -> str:
        return self._data["outcome"]

    @property
    def finalized(self) -> bool:
        return self._data["outcome"] != "running"

    def begin(self) -> Optional[str]:
        """Write the in-progress record; returns the path (None if the
        write failed — telemetry never takes a run down)."""
        return self._write()

    def disable(self) -> None:
        """Stop writing (non-zero processes of a multi-host run share the
        log dir; only process 0 may own the manifest file)."""
        with self._lock:
            self._enabled = False

    def set_config(self, config: Optional[dict]) -> None:
        with self._lock:
            self._data["config"] = config
        self._write()

    def note(self, key: str, value: Any) -> None:
        """Record one machine-readable fact (replication fallback, cost
        model source, probe timings...). Last write per key wins."""
        with self._lock:
            self._data["notes"][key] = value
        self._write()

    def set_metrics(self, metrics: dict) -> None:
        """Merge flat scalar metrics (e.g. ``GoodputLedger.flat_metrics``:
        ``goodput/mfu``, ``goodput/flops/<comp>_frac``, ...)."""
        with self._lock:
            for k, v in (metrics or {}).items():
                self._data["metrics"][k] = v
        self._write()

    def finalize(
        self,
        outcome: str,
        *,
        error: Optional[str] = None,
        exit_code: Optional[int] = None,
        metrics: Optional[dict] = None,
        notes: Optional[dict] = None,
    ) -> bool:
        """Stamp the terminal outcome; True iff this call won the race."""
        if outcome not in OUTCOMES:
            raise ValueError(
                f"unknown outcome {outcome!r}; use one of {OUTCOMES}"
            )
        with self._lock:
            if self._data["outcome"] != "running":
                return False
            self._data["outcome"] = outcome
            self._data["error"] = error
            self._data["exit_code"] = exit_code
            self._data["finalized_unix"] = round(float(self._clock()), 3)
            for k, v in (metrics or {}).items():
                self._data["metrics"][k] = v
            for k, v in (notes or {}).items():
                self._data["notes"][k] = v
        self._write()
        return True

    def move_to(self, path: str) -> None:
        """Re-home the manifest (config resolution can change the log
        dir after the early, pre-probe record was written)."""
        with self._lock:
            old = self.path
            self.path = path
        self._write()
        if old != path:
            try:
                os.remove(old)
            except OSError:
                pass

    # ----------------------------------------------------------------- I/O

    def _write(self) -> Optional[str]:
        with self._lock:
            if not self._enabled:
                return None
            payload = json.dumps(self._data, indent=2, default=str)
            path = self.path
        try:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(payload)
            os.replace(tmp, path)  # atomic: a crash mid-write cannot tear
            return path
        except OSError:
            return None

    @classmethod
    def load(cls, path: str) -> dict:
        with open(path) as f:
            return json.load(f)


# ---------------------------------------------------------- record reading


@dataclasses.dataclass
class RunRecord:
    """One history entry, normalized: infra failure or measurement."""

    label: str
    order: float
    outcome: str
    metrics: dict[str, float]  # throughput / mfu / input_wait_frac
    detail: str
    raw: dict

    @property
    def ok(self) -> bool:
        return self.outcome == "ok"


def _bench_line_metrics(parsed: dict) -> dict[str, float]:
    out: dict[str, float] = {}
    if isinstance(parsed.get("value"), (int, float)):
        out["throughput"] = float(parsed["value"])
    if isinstance(parsed.get("mfu"), (int, float)):
        out["mfu"] = float(parsed["mfu"])
    goodput = parsed.get("goodput") or {}
    frac = (goodput.get("fractions") or {}).get("input_wait")
    if isinstance(frac, (int, float)):
        out["input_wait_frac"] = float(frac)
    # Measured attention-core time share (bench --trace; traceview):
    # present only on traced benches — the sentinel skips records
    # without it rather than inventing a number.
    acf = parsed.get("attention_core_frac")
    if isinstance(acf, (int, float)):
        out["attention_core_frac"] = float(acf)
    # Serving records (tools/serve_bench.py): p99 latency (lower-better)
    # and request throughput (higher-better). Deliberately NOT folded
    # into "throughput" — img/s/chip and req/s are different animals,
    # and mixing them would poison both baselines. Training-only records
    # are skipped for these, never zero-filled (the
    # attention_core_frac contract).
    #
    # Quantized-weights serving lines (serve_bench --quant-weights,
    # ISSUE 17) carry ``quant: "int8"`` and score under the quant_*
    # metric names instead: int8 and bf16 latencies are different
    # baselines (different executables, different HBM traffic), and one
    # history must never contaminate the other — the same isolation the
    # fleet_* metrics get. Float lines lack the key and keep the plain
    # names; skip-not-zero-fill holds on both sides.
    quant_prefix = "quant_" if parsed.get("quant") == "int8" else ""
    for key in ("p99_latency_ms", "serve_throughput", "slo_hit_frac"):
        value = parsed.get(key)
        if isinstance(value, (int, float)):
            out[quant_prefix + key] = float(value)
    # Fleet records (tools/serve_bench.py --replicas: router-observed
    # fleet latency/throughput over N replicas). Deliberately separate
    # from the single-engine serve metrics — one replica's p99 and the
    # fleet's p99 are different SLOs with different baselines, and a
    # fleet record must not contaminate the single-engine history (nor
    # vice versa). Same skip-not-zero-fill contract.
    for key in (
        "fleet_p99_latency_ms", "fleet_throughput", "fleet_headroom_frac",
    ):
        value = parsed.get(key)
        if isinstance(value, (int, float)):
            out[key] = float(value)
    # Router tracing overhead (ISSUE 16 — traced fleet records only;
    # older fleet lines lack it and are skipped, never zero-filled).
    roh = parsed.get("router_overhead_ms")
    if isinstance(roh, (int, float)):
        out["router_overhead_ms"] = float(roh)
    # Prediction-quality headline (ISSUE 20, docs/quality.md): shadow
    # agreement (fleet runs with a shadow rank) and probe health (runs
    # with --probe-every). Present only when the features ran — absent
    # records are skipped by the sentinel, never zero-filled: a run
    # without probes is not "every probe failed".
    for key in ("quality_agreement", "probe_ok_frac"):
        value = parsed.get(key)
        if isinstance(value, (int, float)):
            out[key] = float(value)
    return out


def _manifest_metrics(metrics: dict) -> dict[str, float]:
    out: dict[str, float] = {}
    value = metrics.get("value")
    if isinstance(value, (int, float)):
        out["throughput"] = float(value)
    mfu = metrics.get("goodput/mfu", metrics.get("mfu"))
    if isinstance(mfu, (int, float)):
        out["mfu"] = float(mfu)
    wall = metrics.get("goodput/wall_s")
    wait = metrics.get("goodput/input_wait_s")
    if (
        isinstance(wall, (int, float))
        and isinstance(wait, (int, float))
        and wall > 0
    ):
        out["input_wait_frac"] = float(wait) / float(wall)
    acf = metrics.get("attention_core_frac")
    if isinstance(acf, (int, float)):
        out["attention_core_frac"] = float(acf)
    # Elastic-training goodput fraction (supervisor chains,
    # sav_tpu/train/supervisor.py): 1 − (lost + backoff)/wall. Present
    # only on supervised runs — like attention_core_frac, absent records
    # are skipped by the sentinel, never zero-filled.
    gf = metrics.get("goodput_frac")
    if isinstance(gf, (int, float)):
        out["goodput_frac"] = float(gf)
    # Serving manifests (kind serve — ServeEngine.stop() writes the
    # LatencyLedger's serve/* flat metrics): surfaced under the sentinel's
    # metric names. Present only on serving runs; training manifests are
    # skipped for these, never zero-filled.
    #
    # Quantized-weights manifests (ServeConfig.quant_weights — the
    # engine stamps serve/quant_weights=1.0 at stop()) score under the
    # quant_* names instead, mirroring the bench-line remap above: the
    # int8 history must never contaminate the bf16 baseline.
    quant_prefix = (
        "quant_" if metrics.get("serve/quant_weights") else ""
    )
    p99 = metrics.get("serve/p99_latency_ms")
    if isinstance(p99, (int, float)):
        out[quant_prefix + "p99_latency_ms"] = float(p99)
    rps = metrics.get("serve/throughput_rps")
    if isinstance(rps, (int, float)):
        out[quant_prefix + "serve_throughput"] = float(rps)
    # Serving SLO hit fraction (r11+ serve telemetry —
    # sav_tpu/serve/telemetry.py SLOTracker). Present only on serving
    # runs with telemetry; PR-10-era serve manifests and training
    # manifests are skipped, never zero-filled.
    slo = metrics.get("serve/slo_hit_frac")
    if isinstance(slo, (int, float)):
        out[quant_prefix + "slo_hit_frac"] = float(slo)
    # Fleet manifests (kind serve_fleet — serve_bench --replicas):
    # router-level latency/throughput under the sentinel's fleet metric
    # names. Present only on fleet runs; everything else is skipped,
    # never zero-filled (the attention_core_frac contract).
    fp99 = metrics.get("fleet/p99_latency_ms")
    if isinstance(fp99, (int, float)):
        out["fleet_p99_latency_ms"] = float(fp99)
    frps = metrics.get("fleet/throughput_rps")
    if isinstance(frps, (int, float)):
        out["fleet_throughput"] = float(frps)
    roh = metrics.get("fleet/router_overhead_ms")
    if isinstance(roh, (int, float)):
        out["router_overhead_ms"] = float(roh)
    # Capacity/headroom fold (ISSUE 19) — fleet runs with capacity
    # stamps only; skipped, never zero-filled, like the rest.
    head = metrics.get("fleet/headroom_frac")
    if isinstance(head, (int, float)):
        out["fleet_headroom_frac"] = float(head)
    # Prediction-quality metrics (ISSUE 20): single-engine probe health
    # (serve/probe_ok_frac, ServeEngine.stop()) and the fleet fold
    # (fleet/quality_agreement from the router's shadow scorer,
    # fleet/probe_ok_frac = min across replicas). Quality-feature runs
    # only; skip-not-zero-fill on both sides.
    pok = metrics.get("serve/probe_ok_frac")
    if isinstance(pok, (int, float)):
        out["probe_ok_frac"] = float(pok)
    qa = metrics.get("fleet/quality_agreement")
    if isinstance(qa, (int, float)):
        out["quality_agreement"] = float(qa)
    fpok = metrics.get("fleet/probe_ok_frac")
    if isinstance(fpok, (int, float)):
        out["probe_ok_frac"] = float(fpok)
    return out


def normalize_run_record(
    obj: dict, *, label: str = "?", index: int = 0
) -> RunRecord:
    """Fold any of the three record shapes into a :class:`RunRecord`.

    Shapes: the driver's ``BENCH_r*.json`` wrapper (``rc``/``parsed``/
    ``tail``), a raw bench output line (``value``/``unit``), or a
    manifest (``schema``/``outcome``/``metrics``). Infra failures come
    back with a non-``ok`` outcome and empty-or-partial metrics — never
    an exception, so one bad record cannot crash a report over the rest.
    """
    order = float(index)
    if "rc" in obj and "parsed" in obj:  # driver wrapper
        if isinstance(obj.get("n"), (int, float)):
            order = float(obj["n"])
        rc, parsed = obj.get("rc"), obj.get("parsed")
        if rc == 0 and isinstance(parsed, dict):
            inner = normalize_run_record(parsed, label=label, index=index)
            return dataclasses.replace(inner, order=order, raw=obj)
        tail = (obj.get("tail") or "").lower()
        if isinstance(parsed, dict) and parsed.get("outcome") in OUTCOMES:
            outcome = parsed["outcome"]
        elif "backend unreachable" in tail or rc == 3:
            outcome = "backend_unreachable"
        elif rc == 4:
            outcome = "hang"
        else:
            outcome = "error"
        last = (obj.get("tail") or "").strip().splitlines()
        return RunRecord(
            label=label, order=order, outcome=outcome, metrics={},
            detail=f"rc={rc}" + (f": {last[-1][:100]}" if last else ""),
            raw=obj,
        )
    if obj.get("schema") == MANIFEST_SCHEMA and "outcome" in obj:  # manifest
        outcome = obj.get("outcome")
        outcome = outcome if outcome in OUTCOMES else "error"
        metrics = _manifest_metrics(obj.get("metrics") or {})
        return RunRecord(
            label=label, order=order, outcome=outcome, metrics=metrics,
            detail=obj.get("error") or f"{obj.get('kind', 'run')} manifest",
            raw=obj,
        )
    # Raw bench line.
    outcome = obj.get("outcome")
    if outcome not in OUTCOMES:
        outcome = "ok" if isinstance(obj.get("value"), (int, float)) else "error"
    metrics = _bench_line_metrics(obj) if outcome == "ok" else {}
    if outcome != "ok":
        detail = obj.get("error") or outcome
    elif isinstance(obj.get("value"), (int, float)):
        detail = f"{obj.get('value')} {obj.get('unit', '')}".strip()
    elif "serve_throughput" in metrics:
        # Serving lines carry no training "value"; summarize in their
        # own units.
        detail = (
            f"{metrics['serve_throughput']} req/s, "
            f"p99 {metrics.get('p99_latency_ms', '?')} ms"
        )
    elif "quant_serve_throughput" in metrics:
        detail = (
            f"int8 {metrics['quant_serve_throughput']} req/s, "
            f"p99 {metrics.get('quant_p99_latency_ms', '?')} ms"
        )
    elif "fleet_throughput" in metrics or "fleet_p99_latency_ms" in metrics:
        detail = (
            f"fleet {metrics.get('fleet_throughput', '?')} req/s, "
            f"p99 {metrics.get('fleet_p99_latency_ms', '?')} ms"
        )
    else:
        detail = obj.get("metric") or "ok"
    return RunRecord(
        label=label, order=order, outcome=outcome, metrics=metrics,
        detail=detail, raw=obj,
    )


def load_run_history(paths: list) -> list[RunRecord]:
    """Load + normalize + order a list of record files.

    Raises ``OSError``/``ValueError`` for unreadable input (the sentinel
    maps those to its usage/IO exit code 2 — a torn file is an infra
    problem to surface, not a regression verdict).
    """
    records = []
    for i, path in enumerate(sorted(paths)):
        with open(path) as f:
            try:
                obj = json.load(f)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}: not valid JSON ({e})") from e
        if not isinstance(obj, dict):
            raise ValueError(f"{path}: expected a JSON object")
        records.append(
            normalize_run_record(
                obj, label=os.path.basename(path), index=i
            )
        )
    records.sort(key=lambda r: (r.order, r.label))
    return records
