"""Everything that watches ``Trainer.fit``, behind one seam.

``fit`` (sav_tpu/train/trainer.py) is the step loop: next batch, dispatch,
run-ahead cap, log boundary, save and eval cadences. It owns the goodput
ledger and the span tracer, because ``sav:fit/*`` spans are the loop's own
phases. Whatever else wants to know what the loop is doing is a
:class:`FitObserver` in the list :func:`build_observers` returns; ``fit``
calls the list's events and knows no listener by name. docs/observability.md
has the table of events and listeners.

The list is in EXIT order, and every event visits it front to back:

  recorder's crash dump and gauges, the OOM dump while the state is live,
  feeder gauges and ``close``, watchdog stop, the bounded checkpoint drain,
  profiler finalize, fleet close and the merged manifest, sanitizer close,
  replication unsubscribe, MFU gauges, watermark, manifest metrics;

``fit`` then closes its static profiler window, if a crash left it open, and
writes the tracer. The same order serves the other events: at ``log`` the
cost observer adds ``mfu`` before the memory observer adds ``hbm_*`` and
``retraces``; at ``logged`` the recorder dumps an incident before the fleet
heartbeat carries its path.

An observer's module is imported only when its switch is on: a run with
nothing switched on pays for the cost gauges, the watermark and, given a
directory, the heartbeat and the OOM dump's exit test.
"""

from __future__ import annotations

import dataclasses
import sys
import time
import warnings
from typing import Any, Callable, Optional

import jax

from sav_tpu.obs import compile_log
from sav_tpu.obs.costs import (
    publish_cost_gauges,
    publish_mfu_gauges,
    resolve_peak_flops,
    train_step_cost,
)
from sav_tpu.obs.fleet import (
    HeartbeatWriter,
    aggregate_fleet,
    resolve_identity,
    write_fleet_manifest,
)
from sav_tpu.obs.memdump import HbmWatermark
from sav_tpu.obs.memory import hbm_stats
from sav_tpu.utils.flops import compiled_flops


def fleet_identity() -> tuple[int, int]:
    """(index, count) of this process among the writers that share a log
    directory: jax's own, or the ``SAV_FLEET_PROC/_PROCS`` override for
    fleets jax.distributed does not coordinate. Process 0 writes the shared
    files; every process writes its own heartbeat stream."""
    return resolve_identity(jax.process_index(), jax.process_count())


def _failed(exc) -> bool:
    return exc is not None and not isinstance(exc, StopIteration)


class FitObserver:
    """One listener of the loop; every event is a no-op until overridden.

    ``step`` counts completed dispatches (1-based, as logged), except in
    :meth:`before_step`, which gets the index of the step about to run
    (``state.step``). Events run on the training thread, but for
    :meth:`host_batch` on the fed path (the feeder's thread).
    """

    def compiled(self, executable) -> None:
        """The step's executable, once a ``fit``, before its first dispatch."""

    def before_step(self, step: int, state) -> None:
        """Top of an iteration, before the batch is waited for."""

    def after_step(self, step: int) -> None:
        """Dispatched, run-ahead cap applied. Host-only."""

    def first_step(self, state, batch, rng) -> None:
        """Once, after the first :meth:`after_step`: steady state starts."""

    def host_batch(self, batch: dict) -> None:
        """A host batch is about to be placed."""

    def stall(self, step: int) -> None:
        """The ledger flagged the window that ends at ``step``."""

    def log(self, step: int, metrics: dict, steps_since: int, wall_s: float) -> None:
        """Log boundary, metrics fetched: a key added to ``metrics`` reaches
        ``history`` and ``log_fn``. The ``steps_since`` steps since the last
        boundary took ``wall_s`` of wall time."""

    def logged(self, step: int, metrics: dict) -> None:
        """Log boundary, after ``log_fn`` got the finished line."""

    def loop_done(self) -> None:
        """The loop ended normally; the final save and its wait follow."""

    def exit(self, exc, state, feeder) -> None:
        """``fit``'s ``finally``: the exception if there is one, the state
        while it is still live, the feeder if the fed path ran."""


EVENTS = (
    "compiled", "before_step", "after_step", "first_step", "host_batch",
    "stall", "log", "logged", "loop_done", "exit",
)


class FitObservers:
    """The observers in exit order. Each event is a method of this object
    that calls, front to back, the observers that override it; they are
    resolved once, here, so a step pays for its listeners only: it looks
    nothing up and a run with nothing switched on calls nobody."""

    def __init__(self, observers, recorder=None):
        self.observers = tuple(observers)
        self.recorder = recorder  # evaluate()'s recorder=: an eval pass is not a step
        for event in EVENTS:
            setattr(self, event, self._fan_out(event))

    def _fan_out(self, event: str) -> Callable:
        listeners = tuple(
            getattr(o, event) for o in self.observers
            if getattr(type(o), event) is not getattr(FitObserver, event)
        )

        def call(*args) -> None:
            for listener in listeners:
                listener(*args)

        call.listeners = listeners
        return call

    def wrap_place(self, place: Callable) -> Callable:
        """The fed path's :meth:`FitObserver.host_batch`: on the feeder's
        thread, overlapped with device compute like the placement itself."""
        if not self.host_batch.listeners:
            return place

        def observed(batch):
            self.host_batch(batch)
            return place(batch)

        return observed


@dataclasses.dataclass(frozen=True)
class _Run:
    """What any observer may use of the run."""

    ledger: Any
    tracer: Any
    manifest: Any  # None when the caller keeps no manifest
    obs_dir: Optional[str]
    start_step: int

    def at_step(self) -> int:
        return self.start_step + self.ledger.steps

    def gauges(self, prefix: str, stats: dict) -> None:
        for k, v in stats.items():
            self.ledger.set_gauge(f"{prefix}/{k}", v)


class _Recorder(FitObserver):
    """Flight recorder (obs/recorder.py; docs/incident_replay.md): a host-side
    ring of step context, raw batches and periodic pre-step snapshots, dumped
    as a replayable bundle on nonfinite metrics, loss spikes, hangs, crashes.
    The per-step path is sync-free (savlint SAV111)."""

    def __init__(self, recorder, run):
        self.recorder, self._run = recorder, run

    def host_batch(self, batch):
        self.recorder.observe_batch(batch)

    def before_step(self, step, state):
        if self.recorder.wants_snapshot(step):
            # The one sync recording adds: a pre-step state copy every
            # record_snapshot_every steps, so a bundle replays from nearby.
            self.recorder.snapshot(step, jax.device_get(state))  # savlint: disable=SAV101 -- periodic pre-step recorder snapshot at the configured cadence, not a per-step sync

    def after_step(self, step):
        self.recorder.on_step(step)

    def logged(self, step, metrics):
        # Detection rides the metrics the boundary already synced.
        trigger = self.recorder.note_metrics(step, metrics)
        if trigger and self.recorder.dump_incident(trigger, step) is not None:
            self._run.tracer.instant("fit/incident", step=step, trigger=trigger)

    def exit(self, exc, state, feeder):
        recorder = self.recorder
        # A failure that dumped on its way out (a nonfinite mid-fit eval
        # dumps 'eval_nonfinite' and THEN raises under debug_nans) gets no
        # second bundle at the same step.
        already_dumped = bool(recorder.incidents) and (
            recorder.incidents[-1]["step"] == (recorder.last_step or 0)
        )
        if _failed(exc) and not already_dumped:
            # debug_nans raises per step, before the boundary's detection
            # ever sees it: dump what the ring holds.
            recorder.dump_incident(
                "nonfinite" if isinstance(exc, FloatingPointError)
                else "exception",
                error=repr(exc),
            )
        self._run.gauges("recorder", recorder.stats())


class _MemDump(FitObserver):
    """Memory forensics on allocator exhaustion (obs/memdump.py;
    docs/profiling.md). The state is live only on the way out: by the time
    train.py's handler classifies the exception the buffers are gone."""

    def __init__(self, run, watermark, cost):
        self._run, self._watermark, self._cost = run, watermark, cost

    def exit(self, exc, state, feeder):
        if not _failed(exc):
            return
        from sav_tpu.obs.manifest import classify_exception
        from sav_tpu.obs.memdump import dump_memory_incident

        if classify_exception(exc) == "oom":
            dump_memory_incident(
                self._run.obs_dir, step=self._run.at_step(), error=repr(exc),
                state=state, watermark=self._watermark, cost=self._cost.cost,
                manifest=self._run.manifest,
            )


class _Feeder(FitObserver):
    """The feeder's worker-side counters as ``feeder/*`` gauges (overlapped
    background time and queue depths, not training-thread wall time), then
    the worker stopped, so that a mid-run exception cannot leave it blocked
    holding placed device buffers."""

    def __init__(self, run):
        self._run = run

    def exit(self, exc, state, feeder):
        if feeder is not None:
            self._run.gauges("feeder", feeder.stats())
            feeder.close()


class _Watchdog(FitObserver):
    """Hang watchdog (obs/watchdog.py). Armed at the top of the second
    iteration, that is once the first step has completed: compile belongs to
    device_check's start-up regime. One beat an iteration, so the deadline
    must exceed the slowest eval pass or checkpoint save. Stopped before the
    final save, which may take as long as the storage needs."""

    def __init__(self, watchdog, start_step):
        self._watchdog, self._start_step, self._started = watchdog, start_step, False

    def before_step(self, step, state):
        if self._started:
            self._watchdog.beat()
        elif step > self._start_step:
            self._watchdog.start()
            self._started = True

    def loop_done(self):
        self._watchdog.stop()

    def exit(self, exc, state, feeder):
        self._watchdog.stop()


class _CheckpointDrain(FitObserver):
    """An abnormal exit must not abandon an in-flight async save: Orbax
    commits by atomic rename, so an un-awaited save is lost, never torn.
    BOUNDED (a crash escaping a wedged filesystem must not inherit the hang)
    and after the watchdog disarms (a slow drain is not a steady-state hang)."""

    def __init__(self, checkpointer, run):
        self._checkpointer, self._run = checkpointer, run

    def exit(self, exc, state, feeder):
        with self._run.tracer.span("fit/checkpoint_wait", bucket="checkpoint"):
            if not self._checkpointer.wait(timeout_s=120.0):
                print(
                    "trainer: in-flight checkpoint save still unfinished "
                    "after 120s; abandoning it (the previous committed step "
                    "remains restorable)",
                    file=sys.stderr,
                )


class _Autoprof(FitObserver):
    """Anomaly-triggered bounded jax.profiler windows (obs/autoprof.py),
    armed by the ledger's stall anomaly, the per-window step-time spike gate
    or the watchdog's soft stage; per process, budgeted like the recorder's
    incidents. Each finished capture is machine-read on the spot
    (obs/traceview.py) against the compiled step's HLO metadata."""

    def __init__(self, cfg, run, process_index, predicted):
        from sav_tpu.obs.autoprof import AutoProfiler

        self._run = run
        self._executable = None
        self._op_index_memo: list = []
        self.profiler = AutoProfiler(
            run.obs_dir,
            trace_steps=cfg.autoprof_steps,
            max_captures=cfg.autoprof_max,
            process_index=process_index,
            manifest=run.manifest,
            op_index_fn=self._op_index,
        )
        # The predicted side of every capture's measured-vs-predicted table
        # (analytic even once XLA's count replaces the total: same keys).
        self.profiler.set_predicted(predicted)

    def _op_index(self):
        # {hlo op -> metadata scope} of the compiled step, from the text of
        # the executable the loop runs. Memoized including failure.
        if not self._op_index_memo:
            index = None
            try:
                from sav_tpu.obs.traceview import parse_hlo_op_index

                text = self._executable.as_text()
                if text:
                    index = parse_hlo_op_index(text)
            except Exception:
                index = None
            self._op_index_memo.append(index)
        return self._op_index_memo[0]

    def compiled(self, executable):
        self._executable = executable

    def before_step(self, step, state):
        # Host-side state machine: starts an armed capture at this step
        # boundary, stops one whose window is over. No device sync.
        self.profiler.on_step(step)

    def stall(self, step):
        self.profiler.request("stall_anomaly", step)

    def log(self, step, metrics, steps_since, wall_s):
        # The host's view of a step (input and collective wait included,
        # unlike the ledger's dispatch window) through the spike gate.
        self.profiler.note_window(step, wall_s / max(steps_since, 1))

    def exit(self, exc, state, feeder):
        # A capture open at the exit is finished at the CURRENT step, so
        # its per_step_ms stays honest.
        self.profiler.finalize(self._run.at_step())
        self._run.gauges("autoprof", self.profiler.stats())


class _Fleet(FitObserver):
    """Fleet heartbeats (obs/fleet.py; docs/fleet.md): EVERY process appends
    to its own ``fleet/proc_<i>.jsonl``, one host-only line a log boundary
    (savlint SAV112); fleet process 0 writes the merged manifest at exit."""

    def __init__(self, writer, run, recorder):
        self.writer, self._run, self._recorder = writer, run, recorder

    def logged(self, step, metrics):
        incidents = self._recorder.incidents if self._recorder is not None else None
        self.writer.beat(
            step, ledger=self._run.ledger, metrics=metrics,
            incident=incidents[-1]["path"] if incidents else None,
        )

    def exit(self, exc, state, feeder):
        run = self._run
        run.gauges("fleet", self.writer.stats())
        self.writer.close(outcome="error" if _failed(exc) else "ok")
        if self.writer.process_index != 0:
            return
        # Fleet process 0's in-run view of step skew, stragglers and dead
        # hosts (offline tools recompute over the final streams).
        try:
            summary = aggregate_fleet(run.obs_dir)
            path = write_fleet_manifest(run.obs_dir, summary)
            if run.manifest is not None and path is not None:
                run.manifest.note("fleet", {
                    "path": path,
                    "processes": {
                        p: {
                            "heartbeats": v.get("heartbeats"),
                            "last_step": v.get("last_step"),
                            "outcome": v.get("outcome"),
                        }
                        for p, v in summary.get("processes", {}).items()
                    },
                    "step_skew": summary.get("step_skew"),
                    "straggler": (
                        summary.get("straggler") or {}
                    ).get("straggler"),
                    "suspects": [
                        s.get("proc") for s in summary.get("suspects", [])
                    ],
                })
        except Exception:
            pass  # fleet aggregation is telemetry, never fatal


class _Sanitizer(FitObserver):
    """Runtime sanitizer (analysis/sanitize.py): from the second step on,
    an implicit host->device transfer is a hard error at the step that
    caused it. The guard is a thread-local context and unwinds on this
    thread before ``fit`` returns."""

    def __init__(self, sanitizer):
        self._sanitizer = sanitizer

    def first_step(self, state, batch, rng):
        self._sanitizer.arm()

    def exit(self, exc, state, feeder):
        self._sanitizer.close()


class _SeqReplication(FitObserver):
    """Sequence-parallel batch-replication fallback: the trace-time event
    surfaces ONCE a fit (a warning, a span instant, a gauge, a manifest
    note), not as a warning a call: degraded parallelism must be
    machine-visible, not log spam."""

    def __init__(self, run):
        from sav_tpu.parallel import seq_parallel

        self._run, self._seen = run, False
        self._unsubscribe = seq_parallel.on_batch_replication(self._on_event)

    def _on_event(self, info):
        if self._seen:
            return
        self._seen = True
        warnings.warn(
            "sequence-parallel batch-replication fallback: batch "
            f"{info['batch']} does not divide the mesh's data-axis product "
            f"{info['data_axis_product']}; attention memory/compute is "
            "multiplied by that product for the whole fit (reported once; "
            "see manifest notes.seq_replication_fallback)",
            stacklevel=2,
        )
        self._run.tracer.instant("fit/seq_replication_fallback", **info)
        self._run.ledger.set_gauge("seq/replicated_batch", info["batch"])
        if self._run.manifest is not None:
            self._run.manifest.note("seq_replication_fallback", info)

    def exit(self, exc, state, feeder):
        self._unsubscribe()


class _Cost(FitObserver):
    """The step's cost model (obs/costs.py) and everything read off it: the
    ``flops/*`` gauges and the ``cost_model`` note up front (a crashed run's
    manifest still says where the FLOPs were going), XLA's exact total once
    the step is compiled, ``mfu`` on every log line, and the end-of-run
    ``goodput/mfu`` and ``flops_per_s`` from the ledger's own aggregates.
    The peak is the config's override, the device table's, or the CPU's
    labelled fake; a device the table does not list raises here, before the
    loop."""

    def __init__(self, cfg, run, params):
        self._run = run
        self._peak_flops, self._peak_source = resolve_peak_flops(cfg.peak_flops)
        self.cost = train_step_cost(
            params,
            batch_size=cfg.global_batch_size,
            image_size=cfg.image_size,
            n_devices=len(jax.devices()),
        )
        self._publish()

    def _publish(self):
        cost = self.cost
        publish_cost_gauges(
            self._run.ledger, cost,
            peak_flops=self._peak_flops, peak_source=self._peak_source,
        )
        if self._run.manifest is not None:
            # The machine-readable twin of the flops/* gauges.
            self._run.manifest.note("cost_model", {
                "source": cost.source,
                "flops_per_device": cost.flops,
                "bytes_accessed": cost.bytes_accessed,
                "attribution": cost.attribution,
                "groups": cost.groups,
                "num_tokens": cost.num_tokens,
                "peak_flops": self._peak_flops,
                "peak_flops_source": self._peak_source,
            })

    def compiled(self, executable):
        flops = compiled_flops(executable)
        if flops:
            # XLA's exact per-device count; the attribution fractions stay
            # analytic (the XLA total does not decompose).
            self.cost = dataclasses.replace(
                self.cost, flops=flops, source="xla-cost-analysis"
            )
            self._publish()

    def log(self, step, metrics, steps_since, wall_s):
        if self.cost.flops and self._peak_flops:
            # Per chip: cost-analysis FLOPs are per device (utils/flops.py).
            step_s = max(wall_s, 1e-9) / max(steps_since, 1)
            metrics["mfu"] = self.cost.flops / step_s / self._peak_flops

    def exit(self, exc, state, feeder):
        ledger = self._run.ledger
        publish_mfu_gauges(
            ledger,
            step_flops=self.cost.flops or 0.0,
            peak_flops=self._peak_flops,
            steps=ledger.steps,
            step_seconds=ledger.bucket_seconds("step"),
        )


class _Memory(FitObserver):
    """HBM watermark (obs/memdump.py), sampled at log boundaries (a host-side
    counter read, no sync; {} on the CPU, backfilled once at the exit) and
    stamped into the manifest on every exit path. Under ``diagnostics`` the
    log line also carries ``hbm_*`` and ``retraces``: the backend compiles
    the process made (obs/compile_log.py) since the last boundary, or since
    the first step for the first. The step cannot be among them (the loop
    calls one executable); an eager op on a new shape, a mid-run eval's
    first pass or a listener that jits something is."""

    def __init__(self, run, watermark, diagnostics):
        self._run, self._watermark = run, watermark
        self._diagnostics, self._since = diagnostics, None

    def first_step(self, state, batch, rng):
        # The step's own compile and the set-up's are behind this point.
        self._since = time.perf_counter()

    def log(self, step, metrics, steps_since, wall_s):
        if not self._diagnostics:
            self._watermark.observe()
            return
        hbm = hbm_stats()
        metrics.update(hbm)
        self._watermark.observe(hbm)
        now = time.perf_counter()
        metrics["retraces"] = float(sum(
            r["kind"] == "backend"
            for r in compile_log.log(since=self._since, until=now)
        ))
        self._since = now

    def exit(self, exc, state, feeder):
        final = self._watermark.finalize()
        if final["peak_bytes"]:
            self._run.ledger.set_gauge("hbm/peak_bytes", final["peak_bytes"])
        if self._run.manifest is not None:
            self._run.manifest.note("hbm", final)


class _Manifest(FitObserver):
    """What the run manifest says of the fit: ``backend`` and ``layout`` up
    front; at the exit, crashed or not, the ledger's metrics and which
    backend and block configuration every traced attention shape resolved to
    (filled at trace time, so it exists once the step compiled)."""

    def __init__(self, run, layout, watermark):
        self._run, self._watermark = run, watermark
        device0 = jax.devices()[0]
        run.manifest.note("backend", {
            "platform": device0.platform,
            "device_kind": getattr(device0, "device_kind", None),
            "n_devices": len(jax.devices()),
            "process_count": jax.process_count(),
        })
        run.manifest.note("layout", layout)

    def exit(self, exc, state, feeder):
        from sav_tpu.ops.attention import snapshot_dispatch_log

        self._run.manifest.set_metrics({
            **self._run.ledger.flat_metrics(),
            "hbm_peak_bytes": self._watermark.peak_bytes,
        })
        dispatch = snapshot_dispatch_log()
        if dispatch:
            self._run.manifest.note("attention_dispatch", dispatch)


def build_observers(
    cfg, *, ledger, tracer, manifest, obs_dir: Optional[str],
    identity: tuple[int, int], checkpointer, params, start_step: int,
    layout: dict,
) -> FitObservers:
    """The observers of one ``fit``, in exit order (module docstring).

    ``checkpointer`` is the one whose in-flight save the exit and the
    watchdog drain, ``params`` what the cost model walks, ``layout`` the
    manifest's note. The links between observers are wired here, where both
    ends live: the watchdog's soft stage writes a fleet event and arms the
    profiler, the stall anomaly arms the profiler, the heartbeat carries the
    recorder's last incident, the OOM dump reads the watermark and the cost.
    """
    run = _Run(ledger, tracer, manifest, obs_dir, start_step)
    fleet_proc, fleet_procs = identity
    watermark = HbmWatermark()
    # Built in the order of their notes: backend, layout, cost_model.
    manifest_observer = (
        _Manifest(run, layout, watermark) if manifest is not None else None
    )
    cost = _Cost(cfg, run, params)
    recorder = writer = autoprof = None
    if cfg.record and fleet_proc == 0:
        from sav_tpu.obs.recorder import FlightRecorder

        recorder = FlightRecorder.from_config(
            cfg, obs_dir or ".", manifest=manifest
        )
    if cfg.fleet and obs_dir is not None:
        writer = HeartbeatWriter(
            obs_dir, process_index=fleet_proc, process_count=fleet_procs
        )
    if cfg.autoprof and obs_dir is not None:
        autoprof = _Autoprof(cfg, run, fleet_proc, cost.cost.attribution)

    observers: list = []
    if recorder is not None:
        observers.append(_Recorder(recorder, run))
    if cfg.memdump and obs_dir is not None:
        observers.append(_MemDump(run, watermark, cost))
    observers.append(_Feeder(run))
    if cfg.watchdog_secs:
        from sav_tpu.obs.watchdog import HangWatchdog

        def on_soft(silent_s):
            # Warning stage (watchdog thread, host-only): a fleet event marks
            # WHEN this process stalled, and the profiler arms so that a
            # stall that resumes slowly gets captured.
            if writer is not None:
                writer.fleet_event(
                    "watchdog_soft", silent_s=round(silent_s, 1),
                    at_step=run.at_step(),
                )
            if autoprof is not None:
                autoprof.profiler.request("watchdog_soft", run.at_step())

        observers.append(_Watchdog(HangWatchdog(
            cfg.watchdog_secs, ledger=ledger, tag="train-watchdog",
            manifest=manifest, recorder=recorder,
            # os._exit skips fit's finally: the watchdog drains an in-flight
            # save itself (bounded) before it exits.
            checkpointer=checkpointer,
            soft_deadline_s=cfg.watchdog_soft_secs, on_soft=on_soft,
        ), start_step))
    if checkpointer is not None:
        observers.append(_CheckpointDrain(checkpointer, run))
    if autoprof is not None:
        observers.append(autoprof)
    if writer is not None:
        observers.append(_Fleet(writer, run, recorder))
    if cfg.sanitize:
        from sav_tpu.analysis.sanitize import StepSanitizer

        observers.append(_Sanitizer(StepSanitizer()))
    if cfg.sequence_parallel:
        observers.append(_SeqReplication(run))
    observers.append(cost)
    observers.append(_Memory(run, watermark, cfg.diagnostics))
    if manifest_observer is not None:
        observers.append(manifest_observer)
    return FitObservers(observers, recorder)
