"""Run telemetry — the observability layer for training runs.

Five signals, one design rule each:

- :mod:`sav_tpu.obs.diagnostics` — **in-jit** optimization diagnostics
  (grad/param/update norms, update-to-param ratio, per-layer-group grad
  norms, nonfinite counts) folded into the step-metrics dict so they ride
  the existing per-log ``device_get`` with zero extra transfers.
- :mod:`sav_tpu.obs.spans` — **host-side** span tracer emitting
  Chrome-trace-event JSON (Perfetto-loadable) around ``fit()``'s phases,
  so input-bound vs compute-bound is diagnosable without an XPlane capture.
- :mod:`sav_tpu.obs.compile_log` — what jax traced, lowered, compiled or
  loaded from the persistent cache, on the spans' clock, each record with
  the phase span that caused it (fed by jax's own monitoring events).
- :mod:`sav_tpu.obs.goodput` — wall-time ledger splitting a run into
  compile / step / input-wait / eval / checkpoint / stall buckets, with
  per-window anomaly flags for transient slowdowns.
- :mod:`sav_tpu.obs.memory` — HBM telemetry from ``device.memory_stats()``.
- :mod:`sav_tpu.obs.watchdog` — heartbeat thread that turns a steady-state
  hang (a step that never completes) into
  a stack dump + labeled exit instead of a job that stalls forever.
- :mod:`sav_tpu.obs.costs` — FLOPs/bytes cost model (XLA cost-analysis
  with an analytic per-layer-group fallback) behind the ``goodput/mfu``
  and per-group attribution gauges.
- :mod:`sav_tpu.obs.manifest` — structured run manifests finalized with a
  machine-readable outcome on every exit path, plus the normalized
  run-record reading shared by the report/sentinel tools.
- :mod:`sav_tpu.obs.recorder` — flight recorder: bounded ring of host-side
  step context (batch hash/raw batches, rng recipe, metrics, periodic
  state snapshots) dumped as a replayable incident bundle on nonfinite
  metrics, loss spikes, hangs, or crashes (``tools/replay_step.py``).
- :mod:`sav_tpu.obs.fleet` — cross-process fleet telemetry: per-process
  heartbeat streams (``fleet/proc_<i>.jsonl``), the merged fleet manifest
  with step skew / straggler ranking / dead-host suspicion
  (``tools/fleet_status.py``, docs/fleet.md).
- :mod:`sav_tpu.obs.autoprof` — anomaly-triggered profiling: a goodput
  stall anomaly, a robust step-time spike, or the watchdog's soft stage
  arms a bounded ``jax.profiler`` window, budgeted like the recorder's
  incidents and stamped into the run manifest.

Re-exports are lazy (PEP 562, same pattern as :mod:`sav_tpu.utils`):
:mod:`spans`, :mod:`compile_log`, :mod:`goodput`, and :mod:`watchdog` are
stdlib-only and must stay importable without dragging ``jax`` into the process.
"""

from __future__ import annotations

from sav_tpu._lazy import install_lazy_exports

_EXPORTS = {
    "diagnostics_metrics": "sav_tpu.obs.diagnostics",
    "grad_group_norms": "sav_tpu.obs.diagnostics",
    "nonfinite_count": "sav_tpu.obs.diagnostics",
    "SpanTracer": "sav_tpu.obs.spans",
    "GoodputLedger": "sav_tpu.obs.goodput",
    "hbm_stats": "sav_tpu.obs.memory",
    "HangWatchdog": "sav_tpu.obs.watchdog",
    "WATCHDOG_EXIT_CODE": "sav_tpu.obs.watchdog",
    "StepCost": "sav_tpu.obs.costs",
    "resolve_peak_flops": "sav_tpu.obs.costs",
    "train_step_cost": "sav_tpu.obs.costs",
    "FlightRecorder": "sav_tpu.obs.recorder",
    "HeartbeatWriter": "sav_tpu.obs.fleet",
    "aggregate_fleet": "sav_tpu.obs.fleet",
    "write_fleet_manifest": "sav_tpu.obs.fleet",
    "AutoProfiler": "sav_tpu.obs.autoprof",
    "RunManifest": "sav_tpu.obs.manifest",
    "RunRecord": "sav_tpu.obs.manifest",
    "classify_exception": "sav_tpu.obs.manifest",
    "load_run_history": "sav_tpu.obs.manifest",
    "normalize_run_record": "sav_tpu.obs.manifest",
}

__all__ = list(_EXPORTS)

__getattr__, __dir__ = install_lazy_exports(
    globals(),
    _EXPORTS,
    {"diagnostics", "spans", "compile_log", "goodput", "memory", "watchdog",
     "costs", "manifest", "recorder"},
)
