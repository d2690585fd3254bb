#!/usr/bin/env python
"""Headline benchmark: DeiT-S/16 ImageNet-shape training throughput per chip.

Measures the full jitted train step (forward + backward + AdamW update,
bf16 compute, label smoothing) — the BASELINE.json north-star metric
(target ≥8,000 img/s/chip). Prints exactly one JSON line:

    {"metric": ..., "value": N, "unit": "img/s/chip", "vs_baseline": N, ...}

``value`` is the best-window throughput (a one-chip machine shares its
host's CPU cores, so windows can show transient slowdowns; the minimum
step time is the hardware-capability number) and
``median_img_per_sec_per_chip`` is the median window — both reported so
the methodology is transparent. ``mfu`` is model-FLOPs utilization from
the compiled step's XLA cost analysis against the chip's peak bf16
FLOP/s. ``goodput`` is the run's wall-time ledger (sav_tpu.obs.goodput, docs/observability.md):
compile / step / input-wait buckets plus the per-window stall anomalies
that make the >5x transient slowdowns visible in the recorded JSON.

Feeds (``--feed``):
  synthetic — one device-resident batch, re-stepped (pure device number)
  pipeline  — the real tf.data path (JPEG bytes → crops → RandAugment →
              CutMix/MixUp) over an in-memory source, feeding the real
              train step; also reports the host pipeline's own img/s
  savrec    — the native SavRecord mmap loader feeding the train step

Fed loops run through the async double-buffered device feeder by default
(sav_tpu/data/feeder.py — host fetch + device_put of batch N+1 overlap
step N, exactly like Trainer.fit); ``--no-async-feed`` serializes them
for A/B. ``transfer_bytes_per_batch`` makes the wire format visible:
``--device-preprocess`` ships uint8 (≈½ the late-bf16 bytes, ¼ of f32).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 8000.0

# The device check lives in sav_tpu.utils.device_check (shared with
# train.py and the serve tools); imported inside main() so --help never
# pays the sav_tpu import.

def _make_trainer(model_name, batch_size, backend, image_size,
                  device_preprocess=False, augment=None,
                  compilation_cache_dir=None):
    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name=model_name,
        num_classes=1000,
        image_size=image_size,
        compute_dtype="bfloat16",
        attention_backend=None if backend == "auto" else backend,
        global_batch_size=batch_size,
        transpose_images=False,
        clip_grad_norm=1.0,
        device_preprocess=device_preprocess,
        compilation_cache_dir=compilation_cache_dir,
        seed=0,
        **({"augment": augment} if augment is not None else {}),
    )
    return Trainer(config)


def _feed_iterator(feed, batch_size, image_size, tmpdir, device_preprocess=False):
    """Host-side batch stream for the fed modes."""
    import numpy as np

    if feed == "pipeline":
        from sav_tpu.data.pipeline import Split, load

        rng = np.random.default_rng(0)
        n = max(4 * batch_size, 2048)
        images = rng.integers(0, 256, (n, image_size, image_size, 3), np.uint8)
        labels = rng.integers(0, 1000, (n,), np.int64)
        return load(
            Split.TRAIN,
            source=(images, labels),
            is_training=True,
            batch_dims=[batch_size],
            image_size=image_size,
            augment_name="cutmix_mixup_randaugment_405",
            # uint8 (device_preprocess) quarters host->device bytes vs
            # f32; otherwise late bf16 halves them.
            bfloat16=True,
            device_preprocess=device_preprocess,
            seed=0,
            process_index=0,
            process_count=1,
        )
    if feed == "savrec":
        import os

        from sav_tpu.data.records import (
            SavRecDataset,
            savrec_train_iterator,
            write_savrec,
        )

        rng = np.random.default_rng(0)
        n = max(4 * batch_size, 2048)
        path = os.path.join(tmpdir, "bench.savrec")
        if not os.path.exists(path):
            write_savrec(
                path,
                rng.integers(0, 256, (n, image_size, image_size, 3), np.uint8),
                rng.integers(0, 1000, (n,), np.int32),
            )
        ds = SavRecDataset(path)
        return savrec_train_iterator(
            ds, batch_size=batch_size, seed=0,
            normalize=not device_preprocess,
            bfloat16=not device_preprocess,
        )
    raise ValueError(feed)


def _record_window(recorder, step, loss_val, result):
    """One bench window through the flight recorder's gates (shared by the
    synthetic and fed loops): pair the window with its context, run the
    nonfinite/spike detection on the already-synced loss, and stash the
    first incident pointer + trigger into the result dict."""
    if recorder is None:
        return
    recorder.on_step(step)
    trig = recorder.note_metrics(step, {"loss": loss_val})
    if trig:
        inc = recorder.dump_incident(trig, step)
        if inc:
            result.setdefault("incident", inc)
            result.setdefault("incident_trigger", trig)


def run(model_name, batch_size, steps, backend, image_size, reps, feed,
        device_preprocess=False, async_feed=True, compilation_cache_dir=None,
        peak_flops=None, record=False, record_dir=None, attn_tune_cache=None,
        trace=False):
    import jax

    from sav_tpu.data import synthetic_data_iterator
    from sav_tpu.ops.attention import (
        clear_dispatch_log,
        snapshot_dispatch_log,
    )
    from sav_tpu.obs.costs import (
        publish_cost_gauges,
        resolve_peak_flops,
        train_step_cost,
    )
    from sav_tpu.obs.goodput import GoodputLedger

    if attn_tune_cache:
        # Point the 'auto' dispatcher at a measured shape→config table
        # (tools/attn_tune.py output) instead of the checked-in default.
        from sav_tpu.ops.attn_tuning import set_cache_path

        set_cache_path(attn_tune_cache)
    # Attention-dispatch provenance: the resolver logs every traced
    # attention shape's (backend, block config, reason) at trace time;
    # cleared here so the stamped record covers exactly this bench's
    # compile (A/B runs and the sentinel can then attribute a number to
    # the dispatch decision that produced it).
    clear_dispatch_log()

    # Wall-time ledger over the whole measurement (docs/observability.md):
    # compile vs step vs input-wait decomposition plus per-window stall
    # anomalies — transient slowdowns are exactly what separates `value`
    # (best window) from the median.
    ledger = GoodputLedger()

    # Keep both A/B arms doing the same work: the savrec path never mixes
    # on the host, so its device_preprocess trainer must not mix either;
    # the tf.data feed mixes on both sides (host mixes vs device mixes),
    # with the trainer's recipe pinned to the iterator's hard-coded
    # augment_name rather than whatever TrainConfig defaults to.
    trainer = _make_trainer(
        model_name, batch_size, backend, image_size, device_preprocess,
        augment="none" if feed == "savrec" else "cutmix_mixup_randaugment_405",
        # The Trainer places the persistent compile cache, before any
        # compile (sav_tpu/utils/compile_cache.py states the rule).
        compilation_cache_dir=compilation_cache_dir,
    )
    state = trainer.init_state()
    rng = jax.random.PRNGKey(0)
    result: dict = {}
    recorder = None
    if record:
        # Flight recorder at *window* granularity (off by default — bench
        # measures the hot loop and must not instrument inside it): a
        # pre-window state snapshot + the window's loss through the
        # nonfinite/spike gates. A NaN'd bench then carries an incident
        # pointer in its JSON line instead of just a wrong-looking number
        # (docs/incident_replay.md). Window entries are step-sparse, so
        # bundles honestly come out replayable: false.
        from sav_tpu.obs.recorder import FlightRecorder

        recorder = FlightRecorder.from_config(
            trainer.config, record_dir or "runs/bench",
            depth=max(reps, 2), keep_batches=max(reps, 2), snapshot_every=1,
        )
    # Roofline accounting (sav_tpu/obs/costs.py): the synthetic branch
    # upgrades this analytic estimate with the AOT executable's exact XLA
    # cost analysis; the fed branches keep the analytic fallback (their
    # step compiles through the jit dispatch cache).
    peak, peak_source = resolve_peak_flops(peak_flops)
    cost = train_step_cost(
        state.params, batch_size=batch_size, image_size=image_size,
        n_devices=len(jax.devices()),
    )

    if feed == "synthetic":
        batch = next(
            synthetic_data_iterator(
                batch_size=batch_size,
                image_size=image_size,
                num_classes=1000,
                learnable=False,
            )
        )
        sharded = trainer.shard_batch(batch)

        # One AOT compile: the measurement loop runs the same executable the
        # cost analysis comes from (AOT .compile() does not populate the jit
        # dispatch cache, so mixing AOT + jit would compile twice).
        with ledger.measure("compile"):
            step = trainer.compile_train_step(state, sharded, rng)
        cost = train_step_cost(
            state.params, batch_size=batch_size, image_size=image_size,
            compiled=step, n_devices=len(jax.devices()),
        )

        # Warmup. Sync via device_get of the loss value: the host then
        # holds a number only a finished step can produce.
        with ledger.measure("step"):
            for _ in range(2):
                state, metrics = step(state, sharded, rng)
            float(jax.device_get(metrics["loss"]))

        windows = []
        for rep in range(reps):
            if recorder is not None:
                recorder.snapshot(rep * steps, jax.device_get(state))
                recorder.observe_batch(batch)
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step(state, sharded, rng)
            loss_val = float(jax.device_get(metrics["loss"]))
            elapsed = time.perf_counter() - t0
            ledger.note_window(steps, elapsed, step=(rep + 1) * steps)
            windows.append(elapsed / steps)
            _record_window(recorder, (rep + 1) * steps, loss_val, result)

        if trace:
            # One EXTRA profiled window after the measured ones (profiler
            # overhead must not pollute `value`), machine-read on the
            # spot (sav_tpu/obs/traceview.py): the compiled step's HLO
            # metadata attributes device time onto the cost model's
            # component keys, and the measured attention-core fraction
            # rides the JSON line + manifest so the regression sentinel
            # gates on WHERE the time went, not just how much
            # (docs/profiling.md).
            from sav_tpu.obs import traceview
            from sav_tpu.utils import profiler as _prof

            # `value` is fully measured by now: a capture failure
            # (unwritable dir, profiler already active, a crash in the
            # extra window) must degrade to a bench WITHOUT trace
            # fields, never destroy the measurement (see except below).
            # Fresh per-run subdirectory: runs/bench/trace accumulates
            # captures across invocations, and an empty capture (the
            # failure the `if traces:` guard exists for) must read as
            # "no trace", never as a PRIOR run's trace summarized under
            # THIS run's op index and stamped into its sentinel record.
            trace_dir = os.path.join(
                record_dir or "runs/bench", "trace",
                f"{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}",
            )
            try:
                op_index = traceview.parse_hlo_op_index(step.as_text())
                jax.block_until_ready(state)
                _prof.start_trace(trace_dir)
                try:
                    for _ in range(steps):
                        state, metrics = step(state, sharded, rng)
                    float(jax.device_get(metrics["loss"]))
                finally:
                    _prof.stop_trace()
                traces = traceview.find_traces(trace_dir)
                if traces:
                    traceview.save_op_index(
                        os.path.join(
                            os.path.dirname(traces[-1]), "op_index.json"
                        ),
                        op_index,
                    )
                    summary = traceview.summarize(
                        traces[-1], op_index=op_index,
                        predicted=cost.attribution, steps=steps,
                    )
                    # Same artifact contract as autoprof captures: the
                    # full summary next to the trace, so run_report
                    # --trace and trace_report discover it offline.
                    try:
                        with open(
                            os.path.join(
                                os.path.dirname(traces[-1]),
                                "trace_summary.json",
                            ),
                            "w",
                        ) as f:
                            json.dump(summary, f, indent=2)
                    except OSError:
                        pass
                    acf = summary.get("attention_core_frac")
                    result["trace"] = {
                        "path": traces[-1],
                        "per_step_ms": summary.get("per_step_ms"),
                        "idle_frac": summary.get("idle_frac"),
                        "indexed_frac": summary.get("indexed_frac"),
                        "components_frac": summary.get(
                            "components_frac"
                        ),
                        "disagrees": (
                            summary.get("vs_predicted") or {}
                        ).get("disagrees", []),
                    }
                    if acf is not None:
                        result["attention_core_frac"] = round(acf, 4)
            except Exception as e:
                result["trace_error"] = repr(e)[:300]
    else:
        import tempfile

        tmpdir = tempfile.mkdtemp(prefix="sav_bench_")
        # Host-only pipeline rate (how fast the input side alone can go).
        it = _feed_iterator(feed, batch_size, image_size, tmpdir, device_preprocess)
        for _ in range(2):
            next(it)  # warm caches / tf.data autotune
        t0 = time.perf_counter()
        host_steps = max(steps // 2, 5)
        with ledger.measure("input_wait"):
            for _ in range(host_steps):
                next(it)
        host_rate = batch_size * host_steps / (time.perf_counter() - t0)
        result["host_pipeline_img_per_sec"] = round(host_rate, 1)

        # End-to-end: pipeline feeding the real train step.
        it = _feed_iterator(feed, batch_size, image_size, tmpdir, device_preprocess)
        first = next(it)
        with ledger.measure("compile"):
            state, metrics = trainer.train_step(state, first, rng)
            float(jax.device_get(metrics["loss"]))
        # Host->device transfer cost for one batch, measured *after* device
        # compute has run — report it so end-to-end decomposes into
        # host / transfer / device-step. Best of 3 (a shared host shows
        # transient stalls), synced via device_get of a reduction over
        # the placed bytes (see the synthetic branch).
        import jax.numpy as jnp

        # jit caches on the callable object: define the reduction once and
        # run one untimed warm-up so the timed reps measure transfer, not a
        # fresh trace+compile per rep.
        _sum_placed = jax.jit(lambda b: jnp.sum(b.astype(jnp.float32)))
        jax.device_get(_sum_placed(trainer.shard_batch(first)["images"]))
        transfer_s = float("inf")
        with ledger.measure("h2d"):
            for _ in range(3):
                t0 = time.perf_counter()
                placed = trainer.shard_batch(first)
                jax.device_get(_sum_placed(placed["images"]))
                transfer_s = min(transfer_s, time.perf_counter() - t0)
        nbytes = sum(
            getattr(v, "nbytes", 0) for v in first.values()
        )
        result["transfer_ms_per_batch"] = round(transfer_s * 1e3, 1)
        result["transfer_mb_per_s"] = round(nbytes / transfer_s / 1e6, 1)
        # Bytes on the wire per batch: uint8 (--device-preprocess) must
        # come out ≈½ the late-bf16 path's, ¼ of f32 — the lever PERF §7
        # measured directly in fed throughput.
        result["transfer_bytes_per_batch"] = nbytes
        # The measured loop pipelines via the async device feeder (the
        # production fit() path): a background thread fetches + places
        # batch N+1 while the device runs step N. --no-async-feed
        # restores the serial fetch → put → step loop for A/B.
        feeder = None
        if async_feed:
            from sav_tpu.data.feeder import DeviceFeeder

            feeder = DeviceFeeder(
                it, trainer.shard_batch, depth=2, name="bench-feeder"
            )

            def next_placed():
                return next(feeder)
        else:
            def next_placed():
                return trainer.shard_batch(next(it))
        windows = []
        try:
            for rep in range(reps):
                if recorder is not None:
                    recorder.snapshot(rep * steps, jax.device_get(state))
                t0 = time.perf_counter()
                for _ in range(steps):
                    state, metrics = trainer.train_step_placed(
                        state, next_placed(), rng
                    )
                loss_val = float(jax.device_get(metrics["loss"]))
                elapsed = time.perf_counter() - t0
                _record_window(
                    recorder, (rep + 1) * steps, loss_val, result
                )
                # Fed windows interleave host fetch + transfer + device
                # step; the ledger books them as 'step' (end-to-end
                # goodput), with the host-only and transfer shares
                # reported separately above.
                ledger.note_window(steps, elapsed, step=(rep + 1) * steps)
                windows.append(elapsed / steps)
        finally:
            if feeder is not None:
                for k, v in feeder.stats().items():
                    ledger.set_gauge(f"feeder/{k}", v)
                feeder.close()

    if recorder is not None:
        for k, v in recorder.stats().items():
            ledger.set_gauge(f"recorder/{k}", v)
    n_chips = len(jax.devices())
    best = min(windows)
    # Cost-model attribution + roofline (docs/perf_accounting.md):
    # cost_analysis FLOPs are per-device → MFU is per chip. Fed-mode MFU
    # is end-to-end (the windows interleave host fetch + transfer with
    # device compute) — lower by construction than the synthetic number.
    publish_cost_gauges(
        ledger, cost, peak_flops=peak, peak_source=peak_source
    )
    result["step_flops_per_device"] = cost.flops
    result["cost_source"] = cost.source
    result["flops_attribution"] = {
        k: round(v, 4) for k, v in cost.attribution.items()
    }
    if cost.flops and peak:
        ledger.set_gauge("flops_per_s", cost.flops / best)
        ledger.set_gauge("mfu", cost.flops / best / peak)
        result["mfu"] = round(cost.flops / best / peak, 4)
        result["peak_flops_source"] = peak_source
        if peak_source != "cpu-fake":
            # The img/s/chip this hardware could do at 100% of its
            # *theoretical* peak — the physical ceiling of the benchmark
            # chip. FLOPs are per-device and the batch is sharded, so
            # the per-chip image share is batch/n_devices. The BASELINE
            # north star (8,000 img/s/chip) was set for a TPU v4 part;
            # when this bound is below the north star, no code on this
            # chip can reach it and vs_baseline must be read against
            # the bound. Suppressed under the CPU fake peak — a bound
            # computed from a made-up number would only mislead.
            per_chip_images = batch_size / n_chips
            result["peak_bound_img_per_sec_per_chip"] = round(
                peak * per_chip_images / cost.flops, 1
            )
    # The resolved attention dispatch (backend + block config per traced
    # shape) — stamped into the JSON line and the run manifest so perf
    # history is attributable to the dispatch decision, not just the
    # requested flag (tools/regression_sentinel.py reads the manifests).
    result["attention_dispatch"] = snapshot_dispatch_log()
    result.update(
        best_step_ms=round(best * 1e3, 2),
        median_img_per_sec_per_chip=round(
            batch_size / statistics.median(windows) / n_chips, 1
        ),
        goodput=ledger.summary(),
    )
    # Flat metric view for the run manifest (main() pops this before
    # printing — underscore-prefixed keys never reach the output JSON).
    result["_manifest_metrics"] = {
        "value": round(batch_size / best / n_chips, 1),
        **ledger.flat_metrics(),
        **(
            {"attention_core_frac": result["attention_core_frac"]}
            if "attention_core_frac" in result else {}
        ),
    }
    return batch_size / best / n_chips, n_chips, result


def _abort_backend_unreachable(args, manifest, error):
    """No TPU and the CPU was not asked for: the run still ends with ONE
    parseable stdout JSON line — ``outcome: "backend_unreachable"``, what
    the device check found, and a pointer to the finalized manifest —
    instead of prose-only stderr that records as ``"parsed": null``. The
    stderr message and exit 3 are device_check's abort contract.
    """
    from sav_tpu.utils.device_check import abort_unreachable

    return abort_unreachable("bench", error, manifest, record={
        "metric": f"{args.model} train img/s/chip (bs={args.batch_size})",
        "value": None,
        "unit": "img/s/chip",
    })


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="deit_s_patch16")
    parser.add_argument("--batch-size", type=int, default=256)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument(
        "--backend",
        default="xla",
        choices=["xla", "fused", "pallas", "auto"],
        help="attention backend: xla (dense), fused (single-pass "
        "short-sequence kernel), pallas (online-softmax flash), or the "
        "three-way measured auto dispatch (short band consults the "
        "attn_tune cache; long band is flash — see PERF.md). The resolved "
        "decision is stamped into the JSON line as attention_dispatch",
    )
    parser.add_argument(
        "--feed",
        default="synthetic",
        choices=["synthetic", "pipeline", "savrec"],
        help="synthetic = device-resident batch; pipeline/savrec = real "
        "input paths feeding the train step",
    )
    parser.add_argument(
        "--reps", type=int, default=4,
        help="timed windows; best and median are both reported",
    )
    parser.add_argument(
        "--device-preprocess", action="store_true",
        help="fed modes ship post-augment uint8 (4x fewer bytes than f32) "
        "and the jitted step normalizes + mixes on device "
        "(TrainConfig.device_preprocess)",
    )
    parser.add_argument(
        "--no-async-feed", action="store_true",
        help="serialize the fed loop (fetch -> device_put -> step on one "
        "thread) instead of the default async double-buffered feeder "
        "(sav_tpu/data/feeder.py) -- the A/B arm for overlap wins",
    )
    parser.add_argument(
        "--compilation-cache-dir", default=None,
        help="override of the persistent XLA compile cache's default "
        "directory (on a TPU: .jax_cache/ in the checkout); loses to the "
        "JAX_COMPILATION_CACHE_DIR variable "
        "(sav_tpu/utils/compile_cache.py)",
    )
    parser.add_argument(
        "--peak-flops", type=float, default=None,
        help="per-chip peak FLOP/s override for MFU/roofline accounting "
        "(docs/perf_accounting.md); default: the device-kind table, with "
        "a deterministic fake peak on CPU (labeled cpu-fake)",
    )
    parser.add_argument(
        "--attn-tune-cache", default=None,
        help="tools/attn_tune.py shape→config cache for the 'auto' "
        "dispatcher (default: SAV_ATTN_TUNE_CACHE env var, then the "
        "checked-in sav_tpu/ops/attn_tune_cache.json)",
    )
    parser.add_argument(
        "--record", action="store_true",
        help="flight recorder at window granularity (off by default so "
        "the measured loop stays uninstrumented): pre-window state "
        "snapshots + the window losses through the nonfinite/spike "
        "gates; a NaN'd bench then carries an 'incident' bundle pointer "
        "in its JSON line and finalizes outcome: nonfinite "
        "(docs/incident_replay.md)",
    )
    parser.add_argument(
        "--trace", action="store_true",
        help="capture one extra profiled window AFTER the measured ones "
        "and machine-read it (sav_tpu/obs/traceview.py): per-layer-group "
        "device-time attribution vs the cost model, with the measured "
        "attention-core fraction in the JSON line + manifest so the "
        "regression sentinel gates on where time went (synthetic feed "
        "only — the fed loops have no AOT executable to index)",
    )
    parser.add_argument(
        "--manifest", default=None,
        help="run-manifest path (sav_tpu/obs/manifest.py): written at "
        "start, finalized with a machine-readable outcome on every exit "
        "path — including the backend-unreachable abort. Default: a "
        "per-run runs/bench/manifest-<stamp>-<pid>.json, so successive "
        "benches accumulate history instead of overwriting one file "
        "(the sentinel's directory expansion globs manifest*.json)",
    )
    args = parser.parse_args(argv)
    if args.manifest is None:
        args.manifest = os.path.join(
            "runs", "bench",
            f"manifest-{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}.json",
        )
    if args.trace and args.feed != "synthetic":
        parser.error(
            "--trace needs the synthetic feed: attribution reads the AOT "
            "executable's HLO metadata, and only the synthetic loop runs "
            "one"
        )
    if args.device_preprocess and args.feed == "synthetic":
        parser.error(
            "--device-preprocess measures the fed paths (uint8 transfer + "
            "on-device finishing); the synthetic feed ships device-resident "
            "f32 batches, so the combination would mislabel the metric"
        )
    from sav_tpu.obs.manifest import RunManifest, classify_exception

    manifest = RunManifest(args.manifest, kind="bench", argv=sys.argv[1:])
    manifest.begin()
    from sav_tpu.utils.device_check import (
        BackendUnreachableError,
        check_accelerator,
    )

    try:
        check_accelerator()
    except BackendUnreachableError as e:
        return _abort_backend_unreachable(args, manifest, e)

    try:
        value, n_chips, extra = run(
            args.model, args.batch_size, args.steps, args.backend,
            args.image_size, reps=args.reps, feed=args.feed,
            device_preprocess=args.device_preprocess,
            async_feed=not args.no_async_feed,
            compilation_cache_dir=args.compilation_cache_dir,
            peak_flops=args.peak_flops,
            record=args.record,
            record_dir=os.path.dirname(args.manifest) or "runs/bench",
            attn_tune_cache=args.attn_tune_cache,
            trace=args.trace,
        )
    except BaseException as e:
        # Every exit path stays parseable: classify (oom/error/...), put
        # the outcome in the manifest AND on stdout, then re-raise for
        # the traceback + nonzero rc (a bare rc=1 would record as
        # parsed: null — the last stdout line explains instead).
        outcome = classify_exception(e)
        manifest.finalize(outcome, error=repr(e), exit_code=1)
        print(json.dumps({
            "outcome": outcome,
            "error": repr(e)[:500],
            "manifest": manifest.path,
        }))
        raise
    feed_desc = args.feed + (
        " uint8+device-preprocess" if args.device_preprocess else ""
    )
    if args.feed != "synthetic" and args.no_async_feed:
        feed_desc += " serial"
    # Heavy imports stay function-local so --help never pays for them.
    import jax

    manifest_metrics = extra.pop("_manifest_metrics", {})
    # A recorded NONFINITE incident demotes the outcome: the regression
    # sentinel must never score a diverged run's throughput as a
    # measurement. A finite loss_spike incident keeps outcome ok — the
    # timing numbers are still real measurements — but the bundle pointer
    # rides the JSON line and manifest either way.
    outcome = (
        "nonfinite" if extra.get("incident_trigger") == "nonfinite"
        else "ok"
    )
    out = {
        "metric": f"{args.model} train img/s/chip (bs={args.batch_size}, "
        f"bf16, {args.backend} attention, {feed_desc} feed, {n_chips} chip, "
        f"best of {args.reps}x{args.steps}-step windows)",
        "value": round(value, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(value / BASELINE_IMG_PER_SEC_PER_CHIP, 4),
        # The device the number belongs to, as jax reports it ("cpu" only
        # when the CPU was asked for — the device check refuses it
        # otherwise).
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "outcome": outcome,
        "manifest": manifest.path,
    }
    out.update(extra)
    notes = {"metric": out["metric"], "platform": out["platform"]}
    if extra.get("attention_dispatch"):
        notes["attention_dispatch"] = extra["attention_dispatch"]
    if extra.get("trace"):
        notes["trace"] = extra["trace"]
    if extra.get("incident"):
        notes["incident"] = extra["incident"]
    manifest.finalize(
        outcome, exit_code=0, metrics=manifest_metrics, notes=notes,
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
