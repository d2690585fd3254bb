#!/usr/bin/env python
"""Training CLI.

Reference-parity entry point (/root/reference/train.py:130-255: click CLI,
steps math from ImageNet sizes, linear-scaled LR, eval every 5 epochs,
checkpoint every 10) rebuilt on the pjit trainer: one typed TrainConfig, a
single mesh, Orbax restore-on-start, and host-side logging outside the
compiled step (the reference logged from inside pmap — SURVEY.md §2.9 #11).

Examples:
  python train.py --fake-data -m vit_ti_patch16 --image-size 32 --steps 20
  python train.py --data-dir /data/imagenet -m deit_s_patch16 -c /ckpts/run1
"""

from __future__ import annotations

import json
import os
import sys

import click


@click.command(context_settings={"show_default": True})
@click.option("--data-dir", type=str, default=None, help="TFDS/TFRecord root.")
@click.option("--fake-data", is_flag=True, help="Zero batches, no real data.")
@click.option("-m", "--model-name", default="deit_s_patch16")
@click.option("--num-classes", type=int, default=1000)
@click.option("--image-size", type=int, default=224)
@click.option("--batch-size", type=int, default=1024, help="Global batch size.")
@click.option("--num-epochs", type=int, default=300)
@click.option("--warmup-epochs", type=int, default=5)
@click.option("--learning-rate", type=float, default=5e-4, help="Base LR (×bs/512).")
@click.option("--weight-decay", type=float, default=0.05)
@click.option("--label-smoothing", type=float, default=0.1)
@click.option(
    "--ema-decay", type=float, default=None,
    help="Parameter EMA decay (e.g. 0.9999); eval then runs on the "
    "averaged weights (DeiT/CaiT-recipe standard).",
)
@click.option("--clip-grad", type=float, default=1.0)
@click.option("--grad-accum", type=int, default=1,
              help="Micro-batches per optimizer update.")
@click.option(
    "-a", "--augmentation", default="cutmix_mixup_randaugment_405",
    help="Augment-string DSL (SURVEY.md §2.4).",
)
@click.option(
    "--patch-size", type=int, default=None,
    help="Override the model's patch size (e.g. 4 for 32x32 inputs so the "
    "token grid stays meaningful at small resolutions).",
)
@click.option(
    "--backend",
    type=click.Choice(["auto", "xla", "fused", "pallas"]),
    default="auto",
    help="Attention backend: auto = the three-way measured dispatch "
    "(docs/benchmarking.md decision table), or force xla / fused "
    "(single-pass short-sequence kernel) / pallas (flash).",
)
@click.option(
    "--attn-tune-cache", type=str, default=None,
    help="tools/attn_tune.py shape->config cache consulted by the 'auto' "
    "attention dispatch (default: SAV_ATTN_TUNE_CACHE env var, then the "
    "checked-in sav_tpu/ops/attn_tune_cache.json).",
)
@click.option(
    "--logits-dtype", type=click.Choice(["inherit", "float32", "bfloat16"]),
    default="inherit",
    help="Softmax dtype on the XLA attention path. 'inherit' follows the "
    "compute dtype (the reference's semantics; under bf16 it halves the "
    "[B,H,L,L] HBM traffic, −15% step time on v5e). Accuracy-gated equal "
    "to f32 on the digits recipe (tools/logits_dtype_gate.py, PERF.md §6). "
    "'float32' forces f32 softmax under bf16 compute.",
)
@click.option(
    "--quant", type=click.Choice(["int8"]), default=None,
    help="int8 quantized matmuls (AQT-style QAT, sav_tpu/ops/quant.py): "
    "every projection/FFN/head dot runs int8xint8->int32 with per-channel "
    "symmetric scales, STE forward, stochastic-rounded gradient dots; the "
    "attention QK/AV core stays in the compute dtype. The param tree is "
    "identical to the float arm, so checkpoints convert to int8 serving "
    "trees (serve --quant-weights; docs/quantization.md).",
)
@click.option(
    "--remat/--no-remat", default=False,
    help="Rematerialize encoder blocks in the backward pass "
    "(jax.checkpoint): trades ~1/3 more forward FLOPs for O(layers) "
    "activation HBM — for batch/sequence sizes that otherwise OOM.",
)
@click.option("--dtype", type=click.Choice(["bfloat16", "float32"]), default="bfloat16")
@click.option(
    "--layout-preset", type=str, default=None,
    help="Declarative sharding layout (sav_tpu/parallel/layout.py): a "
    "built-in name ('dp' | 'tpN' | 'fsdpN' | '2dXxY') or the path of a "
    "preset JSON emitted by tools/mesh_tune.py. States the mesh AND "
    "every param/activation spec in one object; mutually exclusive with "
    "--tp/--fsdp/--sp/--pp. Stamped into the manifest as notes.layout.",
)
@click.option("--tp", type=int, default=1, help="Tensor-parallel mesh axis size.")
@click.option("--fsdp", type=int, default=1, help="FSDP mesh axis size (params sharded).")
@click.option(
    "--sp", type=int, default=1,
    help="Sequence-parallel mesh axis size: every self-attention core "
    "shards its sequence over a 'seq' axis (ring attention by default — "
    "exact, CLS-odd lengths handled by pad-and-mask).",
)
@click.option(
    "--sp-method", type=click.Choice(["ring", "ulysses"]), default="ring",
    help="SP strategy: 'ring' streams K/V by ppermute (any head count); "
    "'ulysses' uses two all-to-alls (needs heads % sp == 0).",
)
@click.option(
    "--pp", type=int, default=1,
    help="Pipeline-parallel stage count: a ViT-family encoder stack is "
    "split into S stages over a 'pipe' mesh axis and run on the GPipe "
    "microbatch schedule (sav_tpu/models/pipelined.py). Composes with "
    "data parallelism; not with --tp/--fsdp/--sp.",
)
@click.option(
    "--pp-microbatches", type=int, default=8,
    help="GPipe microbatch count M (bubble fraction (S-1)/(M+S-1)); the "
    "per-data-shard batch must be divisible by it.",
)
@click.option(
    "--preset", type=str, default=None,
    help="Named experiment preset (sav_tpu.train.presets); CLI flags override.",
)
@click.option("-c", "--checkpoint-dir", type=str, default=None)
@click.option(
    "--checkpoint-every-steps", type=int, default=None,
    help="Step-granular checkpoint cadence (docs/elasticity.md): save "
    "once >= N steps passed since the last save, in addition to "
    "--checkpoint-every-epochs. Fires at the log boundary (whose metrics "
    "sync already drained the pipeline; a misaligned --log cadence "
    "delays a save by at most one log window) with Orbax async writes — "
    "no extra step-time pause — and makes resume step-exact mid-epoch.",
)
@click.option(
    "--checkpoint-every-secs", type=float, default=None,
    help="Wall-clock checkpoint cadence: save when this many seconds "
    "passed since the last save (checked at log boundaries). Composes "
    "with the step/epoch cadences; size it to the wall time you can "
    "afford to re-pay after a preemption.",
)
@click.option(
    "--supervise", is_flag=True,
    help="Elastic-training supervisor mode (docs/elasticity.md): run "
    "this same command as a child process under bounded-restart "
    "supervision — device-check exit 3, watchdog exit 4, crashes, and "
    "signal kills restart with exponential backoff; resume is the "
    "trainer's own step-exact restore from -c. Writes the manifest "
    "chain to <log-dir>/supervisor.json (goodput/lost_s accounting, "
    "rewind-and-skip of nonfinite incident batches). Requires -c. The "
    "supervisor process never imports jax.",
)
@click.option(
    "--max-restarts", type=int, default=16,
    help="Supervisor restart budget (attempts = restarts + 1).",
)
@click.option(
    "--restart-backoff", type=float, default=5.0,
    help="Supervisor restart backoff base, seconds (doubles per "
    "restart, capped at 300; deterministic — no jitter).",
)
@click.option(
    "--skip-steps", type=str, default=None,
    help="Rewind-and-skip (docs/elasticity.md): comma-separated "
    "1-indexed schedule steps whose batches are dropped once — the "
    "PaLM-style cure for a data-caused NaN. Normally passed by the "
    "supervisor after a nonfinite incident (the flight recorder's "
    "bundle names the step); each dropped batch's blake2b fingerprint "
    "is noted into the manifest (notes.rewind_skip).",
)
@click.option(
    "--synth-data", is_flag=True,
    help="Deterministic counter-based synthetic batches "
    "(sav_tpu/data/synthetic.py): each batch is a pure function of "
    "(seed, step), so the stream is resumable by construction and an "
    "external verifier can recompute any position's batch hash. TF-free "
    "— the elasticity soak/kill-resume data path.",
)
@click.option(
    "--debug-nans/--no-debug-nans", default=False,
    help="Assert every step's metrics are finite (host-side check per "
    "step — a per-step device sync, debug only): the run dies with "
    "outcome 'nonfinite' at the exact bad step instead of training on "
    "through NaN, and with --record the flight recorder dumps the "
    "offending batch for rewind-and-skip.",
)
@click.option(
    "--init-from", type=str, default=None,
    help="Warm-start params/batch_stats from another run's checkpoint dir "
    "(fresh step/optimizer). Cross-resolution finetunes resample the "
    "pos_embed tables (the 224-pretrain -> 384-finetune ViT recipe); "
    "other shape mismatches keep fresh init. A resumable checkpoint in "
    "-c takes precedence (preemption-safe resume beats re-warm-starting).",
)
@click.option(
    "--eval-only", is_flag=True,
    help="Restore from -c and run one evaluation pass; no training.",
)
@click.option("--steps", type=int, default=None, help="Override total steps.")
@click.option(
    "--num-train-images", type=int, default=None,
    help="Train-split size for non-ImageNet TFRecord datasets "
    "(disables the 10k VALID carve-out and the 1-indexed label shift).",
)
@click.option(
    "--num-eval-images", type=int, default=None,
    help="Eval-split size for non-ImageNet TFRecord datasets.",
)
@click.option(
    "--crop-min-area", type=click.FloatRange(0.0, 1.0, min_open=True),
    default=0.08,
    help="Lower bound of the Inception-crop area range (reference parity "
    "0.08). Small-image datasets want a gentler floor, e.g. 0.5.",
)
@click.option(
    "--train-flip/--no-train-flip", default=True,
    help="Random horizontal flip in train preprocessing (off for datasets "
    "with chirality, e.g. digits/text).",
)
@click.option(
    "--platform", type=click.Choice(["auto", "cpu"]), default="auto",
    help="'cpu' pins JAX to the host CPU before backend init (same as "
    "JAX_PLATFORMS=cpu) — for smoke runs and rehearsals. With 'auto' the "
    "run must find a TPU: anything else aborts with exit 3 and the "
    "backend_unreachable manifest outcome.",
)
@click.option(
    "--log-dir", type=str, default=None,
    help="Telemetry sink: metrics.jsonl, goodput.json and (with "
    "--trace-spans) spans.trace.json land here. Default: the checkpoint "
    "dir if given, else runs/<model-name>. Render with tools/run_report.py.",
)
@click.option(
    "--diagnostics/--no-diagnostics", default=False,
    help="In-jit optimization diagnostics in the step metrics (param/"
    "update norms, update-to-param ratio, per-layer-group grad norms, "
    "nonfinite counts) plus HBM telemetry and the compiles since the "
    "last line (`retraces`, from the compile log) at log time; rides "
    "the existing per-log device_get, zero extra transfers "
    "(docs/observability.md).",
)
@click.option(
    "--trace-spans/--no-trace-spans", default=False,
    help="Record host-side spans around fit()'s phases (batch fetch, "
    "shard/H2D, step dispatch, log sync, eval, checkpoint) into a "
    "Perfetto-loadable <log-dir>/spans.trace.json.",
)
@click.option(
    "--watchdog-secs", type=float, default=None,
    help="Hang watchdog: when no step completes within this many seconds "
    "the run dumps all thread stacks + the goodput ledger and aborts with "
    "exit 4 (the device check's exit 3 = never started; 4 = hung mid-run). "
    "Armed after the first step; size it above the slowest eval/"
    "checkpoint gap.",
)
@click.option(
    "--watchdog-soft-secs", type=float, default=None,
    help="Watchdog soft (warning) stage: when no step completes within "
    "this many seconds (< --watchdog-secs) dump all thread stacks + a "
    "fleet-heartbeat event and arm the anomaly profiler, but keep "
    "running — only the hard deadline aborts (docs/fleet.md).",
)
@click.option(
    "--fleet/--no-fleet", default=True,
    help="Fleet telemetry (docs/fleet.md): every process appends "
    "heartbeats (step, goodput buckets, HBM/retraces, incident pointer) "
    "to <log-dir>/fleet/proc_<i>.jsonl at the log boundary (no extra "
    "device syncs), and process 0 writes the merged fleet manifest "
    "(step skew, straggler ranking, dead-host suspicion). Render with "
    "tools/fleet_status.py or run_report.py --fleet.",
)
@click.option(
    "--autoprof/--no-autoprof", default=False,
    help="Anomaly-triggered profiling (docs/fleet.md): a goodput stall "
    "anomaly, a robust step-time spike, or the watchdog's soft stage "
    "arms jax.profiler for a bounded --autoprof-steps trace under "
    "<log-dir>/autoprof/, stamped into the run manifest; at most "
    "--autoprof-max captures per run.",
)
@click.option(
    "--autoprof-steps", type=int, default=4,
    help="Steps per anomaly-triggered profiler capture window.",
)
@click.option(
    "--autoprof-max", type=int, default=2,
    help="Per-run budget of anomaly-triggered profiler captures "
    "(the recorder's max_incidents discipline applied to traces).",
)
@click.option(
    "--memdump/--no-memdump", default=True,
    help="Memory forensics (docs/profiling.md): on an OOM-classified "
    "crash, dump a live-buffer ranking (classified params/opt-state/"
    "unattributed against the cost model's per-group byte estimates), "
    "an HBM snapshot, and a device-memory pprof under "
    "<log-dir>/incidents/memdump_<step>/. The run's peak-HBM watermark "
    "is stamped into the manifest regardless.",
)
@click.option(
    "--record/--no-record", default=False,
    help="Flight recorder (docs/incident_replay.md): keep a bounded ring "
    "of the last steps' host-side context (batch hashes + raw batches, "
    "rng recipe, metrics, periodic pre-step state snapshots) and dump a "
    "replayable incident bundle under <log-dir>/incidents/step_<N>/ on "
    "nonfinite metrics, a loss spike, a watchdog hang, or a crash. "
    "Steady-state cost is host-only bookkeeping; replay with "
    "tools/replay_step.py.",
)
@click.option(
    "--record-depth", type=int, default=16,
    help="Ring-buffer depth (steps of context the recorder retains; the "
    "newest --record-batches of them keep their raw host batches — both "
    "clamp to the depth when it is smaller).",
)
@click.option(
    "--record-batches", type=int, default=4,
    help="Raw host batches the recorder retains (and the pre-step "
    "snapshot cadence ceiling); replay covers at most this many steps "
    "before the incident.",
)
@click.option(
    "--spike-sigma", type=float, default=6.0,
    help="Loss-spike incident gate: flag a logged loss more than this "
    "many scaled MADs above the rolling median of healthy windows "
    "(upward only; 0 disables; armed after 8 healthy windows).",
)
@click.option(
    "--sanitize/--no-sanitize", default=False,
    help="Runtime sanitizer around the steady-state hot loop "
    "(sav_tpu.analysis.sanitize): disallow implicit host->device "
    "transfers on the training thread. Armed after the first completed "
    "step.",
)
@click.option(
    "--device-preprocess/--no-device-preprocess", default=False,
    help="Ship post-augment uint8 batches (4x fewer host->device bytes "
    "than f32) and run normalize + CutMix/MixUp inside the jitted step "
    "with replayable jax.random draws (sav_tpu/ops/preprocess.py).",
)
@click.option(
    "--async-feed/--no-async-feed", default=True,
    help="Async double-buffered device feed (docs/input_pipeline.md): a "
    "background thread fetches host batches and issues the sharded "
    "device_put so transfer of batch N+1 overlaps device step N. "
    "--no-async-feed restores the serial fetch->put->step loop.",
)
@click.option(
    "--feed-depth", type=int, default=2,
    help="Placed batches the async feeder buffers beyond the one in "
    "flight (backpressure bound; placed-batch HBM exposure is 2*depth+2 "
    "-- depth queued + 1 being placed + depth+1 dispatched, see "
    "docs/input_pipeline.md).",
)
@click.option(
    "--compilation-cache-dir", type=str, default=None,
    help="Override of the persistent XLA compile cache's default "
    "directory (on a TPU: .jax_cache/ in the checkout; off on the CPU). "
    "Loses to the JAX_COMPILATION_CACHE_DIR variable "
    "(sav_tpu/utils/compile_cache.py). Restarts load compiled programs "
    "from disk instead of compiling again.",
)
@click.option(
    "--peak-flops", type=float, default=None,
    help="Per-chip peak FLOP/s override for MFU/roofline accounting "
    "(docs/perf_accounting.md). Default: the device-kind table; CPU "
    "resolves to a deterministic fake peak (labeled cpu-fake in the "
    "manifest) so the plumbing is testable off-accelerator.",
)
@click.option("--seed", type=int, default=42)
@click.pass_context
def main(ctx, **kwargs):
    """Training CLI — thin manifest shell around :func:`_run`.

    Every run writes a RunManifest (docs/perf_accounting.md) next to its
    telemetry and finalizes it on every exit path: ok, exception
    (classified into retrace/oom/error), watchdog fire (the watchdog
    finalizes 'hang' itself before exit 4), and backend-unreachable
    (require_accelerator finalizes before exit 3).
    """
    if kwargs.get("supervise"):
        # The supervisor owns <log-dir>/supervisor.json; each child
        # attempt owns manifest.json. No jax import happens on this
        # path — a chip belongs to one process at a time, so the
        # parent of an on-chip job must leave it to the child.
        raise SystemExit(_supervise(kwargs))

    from sav_tpu.obs.manifest import RunManifest, classify_exception

    # Provisional sink: the same default resolution the config does later
    # (_run moves the manifest if preset/config resolution changes it).
    sink = (
        kwargs.get("log_dir")
        or kwargs.get("checkpoint_dir")
        or os.path.join("runs", kwargs.get("model_name") or "run")
    )
    manifest = RunManifest(
        os.path.join(sink, "manifest.json"), kind="train", argv=sys.argv[1:]
    )
    manifest.begin()
    try:
        _run(ctx, manifest, **kwargs)
        if not manifest.finalized:
            manifest.finalize("ok", exit_code=0)
    except (click.ClickException, click.Abort) as e:
        # Usage errors are still finalized — a stale 'running' manifest
        # would read as a run that died too hard to say why.
        manifest.finalize("error", error=repr(e), exit_code=2)
        raise
    except SystemExit as e:
        # The device check finalized 'backend_unreachable' already (finalize
        # is first-wins), but any OTHER sys.exit — a library bailing out,
        # a future ctx.exit — must not strand the manifest at 'running'.
        if not manifest.finalized:
            ok = e.code is None or e.code == 0
            code = e.code if isinstance(e.code, int) else (0 if ok else 1)
            manifest.finalize(
                "ok" if ok else "error",
                error=None if ok else f"SystemExit({e.code!r})",
                exit_code=code,
            )
        raise
    except BaseException as e:
        manifest.finalize(classify_exception(e), error=repr(e), exit_code=1)
        raise


def _supervise(kwargs) -> int:
    """train.py --supervise: re-run this command (sans supervisor flags)
    under :class:`sav_tpu.train.supervisor.Supervisor`."""
    from sav_tpu.train.supervisor import (
        Supervisor,
        parse_skip_steps,
        strip_supervisor_flags,
    )

    if not kwargs.get("checkpoint_dir"):
        # Without a checkpoint dir every restart would begin from step 0
        # — that is a crash loop with extra steps, not elasticity.
        raise click.UsageError(
            "--supervise needs -c/--checkpoint-dir: restarts resume from "
            "its checkpoints"
        )
    sink = kwargs.get("log_dir") or kwargs["checkpoint_dir"]
    # The user's own --skip-steps seeds the supervisor's cumulative skip
    # ledger instead of riding the child argv: the supervisor re-appends
    # the full set every attempt, and two --skip-steps flags would
    # collapse to click's last-value-wins.
    try:
        user_skips = parse_skip_steps(kwargs.get("skip_steps"))
    except ValueError as e:
        raise click.UsageError(str(e))
    child_argv = [sys.executable, os.path.abspath(__file__)]
    child_argv += strip_supervisor_flags(
        sys.argv[1:], extra_value_flags=("--skip-steps",)
    )
    supervisor = Supervisor(
        child_argv,
        log_dir=sink,
        checkpoint_dir=kwargs["checkpoint_dir"],
        max_restarts=kwargs.get("max_restarts", 16),
        backoff_base_s=kwargs.get("restart_backoff", 5.0),
        skip_steps=user_skips,
    )
    return supervisor.run()


def _run(
    ctx, manifest, data_dir, fake_data, model_name, num_classes, image_size,
    batch_size,
    num_epochs, warmup_epochs, learning_rate, weight_decay, label_smoothing,
    ema_decay, clip_grad, grad_accum, augmentation, patch_size, backend,
    attn_tune_cache, logits_dtype,
    quant, remat, dtype, layout_preset, tp, fsdp, sp, sp_method, pp,
    pp_microbatches, preset,
    checkpoint_dir, checkpoint_every_steps, checkpoint_every_secs,
    supervise, max_restarts, restart_backoff, skip_steps, synth_data,
    debug_nans, init_from,
    eval_only, steps, num_train_images,
    num_eval_images, crop_min_area, train_flip, platform,
    log_dir, diagnostics, trace_spans, watchdog_secs,
    watchdog_soft_secs, fleet, autoprof, autoprof_steps, autoprof_max,
    memdump, record, record_depth, record_batches, spike_sigma,
    sanitize, device_preprocess, async_feed, feed_depth,
    compilation_cache_dir, peak_flops, seed,
):
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from sav_tpu.parallel import distributed_init
    from sav_tpu.train import TrainConfig, Trainer, get_preset

    if watchdog_soft_secs is not None and (
        watchdog_secs is None or watchdog_soft_secs >= watchdog_secs
    ):
        # The soft stage rides the hard watchdog's thread; a soft-only
        # (or inverted) configuration would silently never warn.
        raise click.UsageError(
            "--watchdog-soft-secs needs --watchdog-secs and must be "
            "smaller than it (soft warns, hard aborts)"
        )
    if synth_data and (fake_data or data_dir):
        raise click.UsageError(
            "--synth-data is its own data source; drop --fake-data/--data-dir"
        )
    if synth_data and eval_only:
        raise click.UsageError(
            "--eval-only has no synthetic eval split; use --fake-data or "
            "a real --data-dir"
        )
    from sav_tpu.train.supervisor import parse_skip_steps

    try:
        skip = parse_skip_steps(skip_steps)
    except ValueError as e:
        raise click.UsageError(str(e))
    if (num_train_images is None) != (num_eval_images is None):
        # Both flags flip the TFRecord reader into custom-dataset mode
        # (0-indexed labels, no VALID carve-out); mixing modes between train
        # and eval would silently corrupt eval labels. Checked before any
        # cluster rendezvous so usage errors fail fast.
        raise click.UsageError(
            "--num-train-images and --num-eval-images must be passed together"
        )

    # Claim the accelerator for JAX BEFORE the data pipeline pulls in
    # TensorFlow: on single-tenant TPU leases, letting TF probe the device
    # first can deadlock JAX's init (sav_tpu/data/_tf.py hides devices
    # from TF as well — both orderings are defended).
    distributed_init()
    from sav_tpu.utils.device_check import require_accelerator

    # In-process, right after the backend comes up: a TPU, or the CPU
    # asked for in so many words. Finalizes the manifest with outcome
    # 'backend_unreachable' before the exit-3 abort.
    require_accelerator("train", manifest=manifest)
    n_devices = len(jax.devices())
    from sav_tpu.obs.fleet import resolve_identity as _fleet_identity

    if _fleet_identity(jax.process_index(), jax.process_count())[0] != 0:
        # Runs share the log dir; only FLEET process 0 owns the manifest
        # file (same rule as the goodput/span writers). The fleet
        # identity defaults to jax's process index and honors the
        # SAV_FLEET_PROC override, so independent workers sharing a log
        # dir (docs/fleet.md) don't clobber each other's manifest either.
        manifest.disable()

    if not synth_data:
        # The TF-backed pipeline import is skipped entirely on the
        # synthetic path: elasticity soak children restart many times,
        # and TF's import cost would be re-paid on every attempt.
        from sav_tpu.data.pipeline import Split, load

    mesh_axes = None
    if layout_preset and (tp > 1 or fsdp > 1 or sp > 1 or pp > 1):
        # Two sources of layout truth: the preset states its own mesh
        # axes, the per-arm flags would state another.
        raise click.UsageError(
            "--layout-preset states the whole layout (mesh axes included); "
            "drop --tp/--fsdp/--sp/--pp"
        )
    if (
        layout_preset
        and os.path.exists(layout_preset)
        and ctx.get_parameter_source("grad_accum")
        != click.core.ParameterSource.COMMANDLINE
    ):
        # A mesh_tune preset decides the microbatch too: its
        # grad_accum_steps rides along unless --grad-accum was passed
        # EXPLICITLY (an explicit `--grad-accum 1` must win — the A/B
        # against accumulation — so the check is on the parameter
        # source, not the value).
        from sav_tpu.parallel.layout import load_layout_preset

        preset_accum = load_layout_preset(layout_preset)[1].get(
            "grad_accum_steps"
        )
        if preset_accum:
            grad_accum = int(preset_accum)
    if pp > 1 and (tp > 1 or fsdp > 1 or sp > 1):
        raise click.UsageError(
            "--pp composes with data parallelism only; drop --tp/--fsdp/--sp"
        )
    if tp > 1 or fsdp > 1 or sp > 1 or pp > 1:
        parallel = tp * fsdp * sp * pp
        if parallel > n_devices or n_devices % parallel:
            raise click.UsageError(
                f"--tp {tp} x --fsdp {fsdp} x --sp {sp} x --pp {pp} = "
                f"{parallel} must divide the device count ({n_devices}); "
                "the quotient is the data-parallel axis and must be >= 1"
            )
        mesh_axes = {"data": n_devices // parallel}
        if fsdp > 1:
            mesh_axes["fsdp"] = fsdp
        if tp > 1:
            mesh_axes["model"] = tp
        if sp > 1:
            mesh_axes["seq"] = sp
        if pp > 1:
            mesh_axes["pipe"] = pp
            # The batch/microbatch divisibility check runs AFTER preset
            # resolution below — the preset may change the global batch.

    config = TrainConfig(
        model_name=model_name,
        num_classes=num_classes,
        image_size=image_size,
        compute_dtype=dtype,
        attention_backend=None if backend == "auto" else backend,
        attention_tune_cache=attn_tune_cache,
        attention_logits_dtype=(
            None if logits_dtype == "inherit" else logits_dtype
        ),
        quant=quant,
        model_overrides={"remat": True} if remat else None,
        global_batch_size=batch_size,
        augment=augmentation,
        num_epochs=num_epochs,
        warmup_epochs=warmup_epochs,
        base_lr=learning_rate,
        weight_decay=weight_decay,
        label_smoothing=label_smoothing,
        ema_decay=ema_decay,
        clip_grad_norm=clip_grad,
        grad_accum_steps=grad_accum,
        device_preprocess=device_preprocess,
        async_feed=async_feed,
        feed_depth=feed_depth,
        compilation_cache_dir=compilation_cache_dir,
        peak_flops=peak_flops,
        mesh_axes=mesh_axes,
        layout_preset=layout_preset,
        sequence_parallel=sp_method if sp > 1 else None,
        pipeline_parallel=pp if pp > 1 else None,
        pipeline_microbatches=pp_microbatches,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every_steps=checkpoint_every_steps,
        checkpoint_every_secs=checkpoint_every_secs,
        debug_nans=debug_nans,
        log_dir=log_dir,
        diagnostics=diagnostics,
        trace_spans=trace_spans,
        watchdog_secs=watchdog_secs,
        watchdog_soft_secs=watchdog_soft_secs,
        fleet=fleet,
        autoprof=autoprof,
        autoprof_steps=autoprof_steps,
        autoprof_max=autoprof_max,
        memdump=memdump,
        record=record,
        record_depth=record_depth,
        record_batches=record_batches,
        spike_sigma=spike_sigma,
        sanitize=sanitize,
        seed=seed,
        **(
            {"num_train_images": num_train_images}
            if num_train_images is not None
            else {}
        ),
    )
    if preset is not None:
        # Preset supplies the recipe; flags the user explicitly passed on the
        # command line override it.
        explicit = {
            name
            for name in ctx.params
            if ctx.get_parameter_source(name) == click.core.ParameterSource.COMMANDLINE
        }
        flag_to_field = {
            "model_name": "model_name", "num_classes": "num_classes",
            "image_size": "image_size", "dtype": "compute_dtype",
            "batch_size": "global_batch_size", "augmentation": "augment",
            "num_epochs": "num_epochs", "learning_rate": "base_lr",
            "weight_decay": "weight_decay", "label_smoothing": "label_smoothing",
            "clip_grad": "clip_grad_norm", "grad_accum": "grad_accum_steps",
            "checkpoint_dir": "checkpoint_dir", "seed": "seed",
            "checkpoint_every_steps": "checkpoint_every_steps",
            "checkpoint_every_secs": "checkpoint_every_secs",
            "debug_nans": "debug_nans",
            "device_preprocess": "device_preprocess",
            "async_feed": "async_feed", "feed_depth": "feed_depth",
            "compilation_cache_dir": "compilation_cache_dir",
            "attn_tune_cache": "attention_tune_cache",
            "quant": "quant",
            "peak_flops": "peak_flops",
            "log_dir": "log_dir", "diagnostics": "diagnostics",
            "trace_spans": "trace_spans", "watchdog_secs": "watchdog_secs",
            "watchdog_soft_secs": "watchdog_soft_secs",
            "fleet": "fleet", "autoprof": "autoprof",
            "autoprof_steps": "autoprof_steps",
            "autoprof_max": "autoprof_max",
            "memdump": "memdump",
            "record": "record", "record_depth": "record_depth",
            "record_batches": "record_batches",
            "spike_sigma": "spike_sigma",
            "sanitize": "sanitize",
            "layout_preset": "layout_preset",
        }
        overrides = {
            field: getattr(config, field)
            for flag, field in flag_to_field.items()
            if flag in explicit
        }
        if "backend" in explicit:
            overrides["attention_backend"] = None if backend == "auto" else backend
        if "logits_dtype" in explicit:
            overrides["attention_logits_dtype"] = (
                None if logits_dtype == "inherit" else logits_dtype
            )
        if mesh_axes is not None:
            overrides["mesh_axes"] = mesh_axes
        if sp > 1:
            overrides["sequence_parallel"] = sp_method
        if pp > 1:
            overrides["pipeline_parallel"] = pp
            overrides["pipeline_microbatches"] = pp_microbatches
        config = get_preset(preset, **overrides)
        if "remat" in explicit:
            # Merge into the preset's overrides rather than replacing them —
            # a preset may carry architecture overrides --remat must not drop.
            import dataclasses as _dc

            mo = dict(config.model_overrides or {})
            if remat:
                mo["remat"] = True
            else:
                mo.pop("remat", None)
            config = _dc.replace(config, model_overrides=mo or None)
    if (config.model_overrides or {}).get("remat"):
        from sav_tpu.models import model_supports

        if not model_supports(config.model_name, "remat"):
            raise click.UsageError(
                f"--remat is only supported by models with a remat field "
                f"(ViT/DeiT family); {config.model_name!r} has none"
            )
    if pp > 1:
        # Validated against the FINAL config (a preset may change the batch
        # or grad-accum). Grad accumulation splits the step's batch before
        # it reaches the pipeline, so the constraint applies per chunk.
        gbs, accum = config.global_batch_size, config.grad_accum_steps
        per_shard = gbs // max(accum, 1) // mesh_axes["data"]
        if gbs % max(accum, 1) or per_shard % pp_microbatches:
            raise click.UsageError(
                f"per-data-shard batch {per_shard} (global {gbs}"
                f"{f' / grad-accum {accum}' if accum > 1 else ''}"
                f" over {mesh_axes['data']} data shards) must be "
                f"divisible by --pp-microbatches {pp_microbatches}"
            )
    if config.log_dir is None:
        # Telemetry always has a sink: metrics.jsonl / goodput.json /
        # spans.trace.json must exist even for flagless smoke runs.
        import dataclasses

        config = dataclasses.replace(
            config,
            log_dir=config.checkpoint_dir
            or os.path.join("runs", config.model_name),
        )
    # The final config may have re-homed the telemetry sink (preset /
    # checkpoint-dir fallback): the manifest follows it, and from here on
    # carries the fully resolved config.
    import dataclasses as _dc

    manifest.move_to(os.path.join(config.log_dir, "manifest.json"))
    manifest.set_config(_dc.asdict(config))
    # Refresh locals the data pipeline uses from the final config.
    model_name = config.model_name
    image_size = config.image_size
    batch_size = config.global_batch_size
    augmentation = config.augment
    dtype = config.compute_dtype
    seed = config.seed
    if jax.process_index() == 0:
        click.echo(config.to_json())

    model = None
    mesh = None
    if patch_size is not None:
        import jax.numpy as jnp

        from sav_tpu.models import create_model

        if config.sequence_parallel:
            # The external model's attention blocks shard_map over the same
            # mesh the trainer pjits on — build it once, share both ways.
            from sav_tpu.parallel import create_mesh

            mesh = create_mesh(config.mesh_axes)
        model = create_model(
            config.model_name,
            num_classes=config.num_classes,
            dtype=jnp.bfloat16 if config.compute_dtype == "bfloat16" else jnp.float32,
            backend=config.attention_backend,
            # Externally built models carry their own logits dtype — thread
            # the config's here or --logits-dtype would silently not apply.
            logits_dtype=config.attention_logits_dtype,
            seq_parallel=config.sequence_parallel,
            seq_mesh=mesh,
            patch_shape=(patch_size, patch_size),
            **(config.model_overrides or {}),
        )
    trainer = Trainer(config, mesh=mesh, model=model)
    # Restore BEFORE building the train stream so the data iterator starts
    # at the restored step: deterministic per-epoch pipelines make resume
    # replay the uninterrupted run's batch schedule (the reference lost
    # iterator position on preemption — train.py never even restored).
    state = trainer.restore_or_init()
    start_step = int(jax.device_get(state.step))
    if init_from and start_step == 0:
        # Only when -c held no resumable checkpoint: a preemption-safe
        # resume must win over re-warm-starting from the pretrain.
        state = trainer.warm_start_from(init_from)

    # Rewind-and-skip shifts the schedule: once position p was dropped,
    # step s >= p consumed a LATER original batch — so a restart that
    # resumes past a skip must rebuild its position-keyed stream from
    # the SHIFTED position, with only the not-yet-reached skips armed
    # (docs/elasticity.md; the supervisor passes the cumulative set on
    # every attempt for exactly this reason).
    from sav_tpu.train.supervisor import resume_schedule_position

    start_pos = resume_schedule_position(start_step, skip)
    skip = {p for p in skip if p > start_pos}

    per_host_batch = batch_size // jax.process_count()

    eval_iter_fn = None
    if not synth_data:
        def eval_iter_fn():
            return load(
                Split.TEST,
                data_dir=data_dir,
                is_training=False,
                batch_dims=[per_host_batch],
                image_size=image_size,
                transpose=config.transpose_images,
                bfloat16=dtype == "bfloat16",
                device_preprocess=config.device_preprocess,
                fake_data=fake_data,
                split_examples=num_eval_images,
            )

    if eval_only:
        if start_step == 0 and not init_from:
            # Freshly initialized weights would produce plausible-looking
            # chance-level metrics — refuse rather than mislead.
            raise click.UsageError(
                "--eval-only found no checkpoint to evaluate: -c holds "
                "none and --init-from was not given"
            )
        eval_iter = eval_iter_fn()
        if fake_data:
            # The fake stream is infinite (it exists to exercise shapes,
            # not epochs) — bound the smoke eval.
            import itertools

            eval_iter = itertools.islice(eval_iter, 4)
        metrics = trainer.evaluate(state, eval_iter)
        if jax.process_index() == 0:
            click.echo(json.dumps({"step": start_step, **metrics}))
        manifest.finalize(
            "ok", exit_code=0,
            metrics={k: float(v) for k, v in metrics.items()},
        )
        return
    if synth_data:
        from sav_tpu.data.synthetic import synth_resumable_iterator

        # Counter-based batches: each is a pure function of (seed, step),
        # so starting at the restored step IS the uninterrupted schedule
        # — step-exact resume with no position bookkeeping to persist.
        train_iter = synth_resumable_iterator(
            seed=seed,
            start_step=start_pos,
            batch_size=per_host_batch,
            image_size=image_size,
            num_classes=config.num_classes,
            transpose=config.transpose_images,
        )
    elif fake_data:
        train_iter = load(
            Split.TRAIN,
            data_dir=data_dir,
            is_training=True,
            batch_dims=[per_host_batch],
            image_size=image_size,
            augment_name=augmentation,
            transpose=config.transpose_images,
            bfloat16=dtype == "bfloat16",
            device_preprocess=config.device_preprocess,
            fake_data=True,
            seed=seed,
        )
    else:
        from sav_tpu.data.pipeline import resumable_train_iterator

        train_iter = resumable_train_iterator(
            Split.TRAIN,
            start_step=start_pos,
            seed=seed,
            data_dir=data_dir,
            batch_dims=[per_host_batch],
            image_size=image_size,
            augment_name=augmentation,
            transpose=config.transpose_images,
            bfloat16=dtype == "bfloat16",
            device_preprocess=config.device_preprocess,
            split_examples=num_train_images,
            crop_area_range=(crop_min_area, 1.0),
            random_flip=train_flip,
        )

    # ---- elasticity layer (docs/elasticity.md) -------------------------
    # Wrapper order matters: chaos injection (env-gated, test-only) sits
    # closest to the source so rewind-and-skip can drop a poisoned batch;
    # the resume probe is outermost so the fingerprint it notes is the
    # batch actually trained next.
    from sav_tpu.train.supervisor import chaos_wrap, skip_step_batches

    train_iter = chaos_wrap(train_iter, start_step=start_pos)
    if skip:
        from sav_tpu.obs.recorder import batch_fingerprint

        skipped_hashes: dict = {}

        def _on_skip(pos, batch):
            skipped_hashes[str(pos)] = batch_fingerprint(batch)["hash"]
            manifest.note("rewind_skip", {
                "steps": sorted(int(k) for k in skipped_hashes),
                "hashes": dict(skipped_hashes),
            })
            click.echo(
                f"rewind-and-skip: dropped the batch at schedule step "
                f"{pos} ({skipped_hashes[str(pos)][:12]}…)",
                err=True,
            )

        train_iter = skip_step_batches(
            train_iter, skip, start_step=start_pos, on_skip=_on_skip
        )
    attempt_env = os.environ.get("SAV_SUPERVISED_ATTEMPT")
    if attempt_env:
        manifest.note("supervisor", {"attempt": int(attempt_env)})
    # Resume provenance: fingerprint the first batch this run trains on
    # (the recorder's blake2b machinery) so supervisors and soak
    # verifiers can prove resume was step-exact against an uninterrupted
    # schedule. Written unconditionally — a restart whose checkpoint
    # never committed resumes from 0, and that fresh start must be as
    # auditable as a mid-epoch one. One hash per run, not per step.
    from sav_tpu.obs.recorder import batch_fingerprint

    def _resume_probe(it, from_step):
        first = True
        for batch in it:
            if first:
                first = False
                manifest.note("resume", {
                    "from_step": from_step,
                    # Original-schedule position the stream restarted
                    # at (== from_step unless rewind-and-skip shifted
                    # the schedule).
                    "schedule_position": start_pos,
                    "skip_steps": sorted(skip),
                    "next_batch_hash": batch_fingerprint(batch)["hash"],
                    "rng": "fold_in(PRNGKey(seed), 1), then "
                           "fold_in(rng, state.step) per step",
                })
            yield batch

    train_iter = _resume_probe(train_iter, start_step)

    writer = None
    if jax.process_index() == 0:
        from sav_tpu.utils.writers import JsonlWriter

        writer = JsonlWriter(config.log_dir)
        click.echo(f"telemetry -> {config.log_dir}", err=True)

    def log_fn(metrics):
        if jax.process_index() == 0:
            click.echo(json.dumps(metrics))
            writer.write(int(metrics.get("step", 0)), metrics)

    try:
        state, history = trainer.fit(
            train_iter,
            num_steps=steps,
            eval_iter_fn=None if fake_data else eval_iter_fn,
            state=state,
            log_fn=log_fn,
            manifest=manifest,
        )
    finally:
        if writer is not None:
            writer.close()
    if jax.process_index() == 0:
        click.echo(f"done at step {int(jax.device_get(state.step))}")


if __name__ == "__main__":
    main()
