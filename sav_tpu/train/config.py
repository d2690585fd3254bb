"""Typed training configuration.

Replaces the reference's three disjoint config systems (click flags,
jaxline ml_collections dicts, and reflection-resolved optimizer names —
SURVEY.md §5 'Config / flag system') with one dataclass that serializes to
JSON next to the checkpoints. Defaults mirror the reference recipe
(/root/reference/train.py:130-220: 300 epochs, lr 5e-4 × bs/512, 5-epoch
warmup cosine, label smoothing 0.1, AdamW-style weight decay).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional


@dataclasses.dataclass
class TrainConfig:
    # Model
    model_name: str = "deit_s_patch16"
    num_classes: int = 1000
    image_size: int = 224
    compute_dtype: str = "bfloat16"
    # None=auto (three-way measured dispatch: fused-short / xla / flash by
    # shape band + the attn_tune cache — see sav_tpu/ops/attention.py) |
    # 'xla' | 'fused' | 'pallas'.
    attention_backend: Optional[str] = None
    # Path to a tools/attn_tune.py shape→config cache consulted by the
    # 'auto' dispatcher (block configs + measured backend winners per
    # attention shape). None = the SAV_ATTN_TUNE_CACHE env var, then the
    # checked-in default table (sav_tpu/ops/attn_tune_cache.json — the
    # PERF.md §5 measurements). Applied process-wide at Trainer
    # construction (trace-time state only; no jitted path reads it).
    attention_tune_cache: Optional[str] = None
    # Softmax dtype on the XLA attention path. None = inherit compute_dtype
    # (the reference's semantics: its logits einsum runs in the model
    # dtype). Under bf16 compute this halves the dominant [B,H,L,L] HBM
    # traffic (−15% step time on v5e, PERF.md §6) at ~2⁻⁸ relative logit
    # precision; accuracy-gated by tools/logits_dtype_gate.py (identical
    # final top-1 under f32 and bf16 compute — gated on the 48² digits
    # recipe only; re-gate on the first full-scale/197+-token run, where
    # bf16 softmax error compounds over more steps). Set 'float32' to
    # force f32 softmax under bf16 compute. Threaded as a model attribute
    # (create_model(..., logits_dtype=...)); ignored when Trainer is
    # handed an externally built model, which carries its own setting.
    attention_logits_dtype: Optional[str] = None
    # int8 quantized projection/FFN dots (sav_tpu/ops/quant.py, ISSUE 17):
    # "int8" = the AQT-style QAT training arm — per-channel symmetric
    # scales, int8×int8→int32 accumulation, STE forward, stochastic-
    # rounded int8 gradient dots (rng rides the trainer's fold_in ladder
    # as a "quant" stream). The param tree is byte-identical to the
    # float arm, so quant checkpoints convert to int8 serving trees via
    # sav_tpu.ops.quant.quantize_params (ServeConfig.quant_weights).
    # Attention QK/AV stays in compute_dtype (PERF §5: not matmul-
    # roofline-bound). None = the plain float path. Threaded as a model
    # attribute (create_model(..., quant=...)); an externally built
    # model carries its own setting.
    quant: Optional[str] = None
    # Extra kwargs for create_model (e.g. {'remat': True} to rematerialize
    # encoder blocks when activations are HBM-bound, or architecture
    # overrides like {'num_layers': 2} for smoke runs). Serialized with the
    # config; must be JSON-representable.
    model_overrides: Optional[dict] = None

    # Device-side batch finishing: the host pipeline ships post-augment
    # uint8 images (4x fewer host->device bytes than f32, 2x fewer than
    # late-bf16) and the jitted steps normalize + apply the augment
    # string's CutMix/MixUp on device with replayable jax.random draws
    # (sav_tpu/ops/preprocess.py). Pair with
    # load(device_preprocess=True) or savrec_train_iterator(normalize=False);
    # the savrec raw path ships NHWC only, so keep transpose_images=False
    # with it (the iterator rejects the combination).
    device_preprocess: bool = False

    # Async device feed (sav_tpu/data/feeder.py; docs/input_pipeline.md):
    # fit()/evaluate() pull batches through a background thread that
    # overlaps host fetch + sharded device_put with device compute
    # (double buffering). False restores the serial fetch→put→step loop
    # (the --no-async-feed escape hatch).
    async_feed: bool = True
    # Placed batches buffered beyond the one in flight (backpressure
    # bound). Placed-batch HBM exposure is feed_depth queued + 1 the
    # worker is placing + feed_depth + 1 dispatched-not-retired (fit and
    # evaluate both cap run-ahead at that); during an epoch-boundary
    # eval inside fit() the train feeder's queue stays full, so the two
    # bounds stack.
    feed_depth: int = 2
    # Override of the persistent compile cache's default directory
    # (sav_tpu/utils/compile_cache.py states the rule: the
    # JAX_COMPILATION_CACHE_DIR variable wins over this; unset, a TPU run
    # caches under the checkout's .jax_cache/ and a CPU run not at all).
    compilation_cache_dir: Optional[str] = None

    # Data
    global_batch_size: int = 1024
    num_train_images: int = 1_281_167  # ImageNet-1k train
    augment: str = "cutmix_mixup_randaugment_405"
    transpose_images: bool = True  # HWCN double-transpose trick

    # Optimization
    num_epochs: int = 300
    base_lr: float = 5e-4  # scaled by global_batch/512 (train.py:214)
    lr_scaling_divisor: int = 512
    end_lr: float = 1e-5
    warmup_epochs: int = 5
    weight_decay: float = 0.05
    clip_grad_norm: Optional[float] = 1.0
    label_smoothing: float = 0.1
    # Parameter EMA (e.g. 0.9999): eval runs on the averaged weights (the
    # DeiT/CaiT-recipe standard). Lives in opt_state
    # (optimizer.track_params_ema), so it checkpoints/shards with the rest;
    # None keeps the opt-state layout of EMA-less checkpoints.
    ema_decay: Optional[float] = None
    aux_loss_weight: float = 0.01  # weight on sown 'losses' (MoE balance etc.)
    grad_accum_steps: int = 1  # micro-batches per optimizer update
    seed: int = 42

    # Mesh: axis name -> size (-1 absorbs remaining devices)
    mesh_axes: Optional[dict] = None
    # Declarative sharding layout (sav_tpu/parallel/layout.py;
    # docs/parallelism.md): a built-in name ('dp' | 'tpN' | 'fsdpN' |
    # '2dXxY') or the path of a preset JSON emitted by
    # tools/mesh_tune.py. States the mesh AND every param/activation
    # spec in one object; mutually exclusive with mesh_axes (two
    # sources of layout truth), stamped into the run manifest as
    # notes.layout. None keeps the mesh_axes-implied layout.
    layout_preset: Optional[str] = None
    # Sequence parallelism: 'ring' | 'ulysses' routes every self-attention
    # core through sav_tpu.parallel.seq_parallel over the mesh's 'seq'
    # axis (mesh_axes must include it; train.py --sp N builds both).
    # Exact numerics incl. CLS-odd lengths (pad-and-mask); self-attention
    # models only, deterministic attention only. Under SP the softmax
    # statistics are always f32 (an online-softmax requirement), so
    # attention_logits_dtype='bfloat16' does not apply, and the per-shard
    # core is dense XLA (attention_backend='pallas' is rejected; the bare
    # parallel.ring_attention op exposes flash mode for divisible lengths).
    sequence_parallel: Optional[str] = None
    # Pipeline parallelism: S > 1 pipelines the encoder stack of a
    # ViT-family model over the mesh's 'pipe' axis (GPipe microbatch
    # schedule, sav_tpu/models/pipelined.py; train.py --pp S builds the
    # mesh). The per-data-shard batch (global_batch_size / grad_accum_steps
    # / data-axis-size) must be divisible by pipeline_microbatches; bubble
    # fraction is (S-1)/(M+S-1). ViT family only; MoE and stage dropout
    # are rejected at construction.
    pipeline_parallel: Optional[int] = None
    pipeline_microbatches: int = 8

    # Logging / checkpointing
    eval_every_epochs: int = 5
    checkpoint_every_epochs: int = 10
    # Step-granular checkpoint cadences (docs/elasticity.md): save every
    # N completed steps and/or every T seconds, in ADDITION to the epoch
    # cadence. Saves fire at the trainer's log boundary — the step's
    # metrics sync already drained the pipeline there, and Orbax's async
    # checkpointing writes on the side — so a cadence adds no step-time
    # pause beyond the host-memory copy; both cadences count from the
    # LAST save, quantized up to the next log boundary (a misaligned
    # log_every_steps coarsens a save by at most one log window, never
    # to the lcm). This is what makes resume
    # step-exact mid-epoch (the resumable data stream replays from the
    # restored step; rng is a pure function of (seed, step)): without a
    # step cadence a preemption loses up to checkpoint_every_epochs of
    # work. None disables either cadence.
    checkpoint_every_steps: Optional[int] = None
    checkpoint_every_secs: Optional[float] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_keep: int = 3
    log_every_steps: int = 100

    # Observability / debugging (SURVEY.md §5 — none of this existed in the
    # reference): optional jax.profiler trace window and NaN guards.
    profile_dir: Optional[str] = None
    profile_start_step: int = 10
    profile_num_steps: int = 5
    debug_nans: bool = False

    # Run telemetry (sav_tpu.obs; docs/observability.md).
    # Sink directory for spans.trace.json / goodput.json (None falls back
    # to checkpoint_dir, then cwd).
    log_dir: Optional[str] = None
    # In-jit optimization diagnostics folded into the step metrics
    # (param/update norms, update-to-param ratio, per-layer-group grad
    # norms, nonfinite counts) plus, at log time, HBM telemetry and
    # `retraces`: the compile log's backend compiles since the last line.
    # Rides the existing per-log device_get — zero extra transfers.
    diagnostics: bool = False
    # Host-side span tracer around fit()'s phases; writes a
    # Chrome-trace-event JSON (Perfetto-loadable) to <log_dir>.
    trace_spans: bool = False
    # Steady-state hang watchdog: abort with exit 4 + full stack dump when
    # no step completes within this many seconds (None disables). Armed
    # after the first step so compile time cannot false-fire it.
    watchdog_secs: Optional[float] = None
    # Watchdog soft (warning) stage (docs/fleet.md): when no step
    # completes within this many seconds — must be < watchdog_secs — the
    # watchdog dumps all thread stacks + a fleet-heartbeat event and arms
    # the anomaly profiler, but the run CONTINUES; only the hard
    # watchdog_secs deadline keeps the exit-4 contract. None disables
    # the soft stage.
    watchdog_soft_secs: Optional[float] = None
    # Fleet telemetry (sav_tpu.obs.fleet; docs/fleet.md): every process
    # appends a heartbeat record (step, goodput buckets, HBM/retrace
    # telemetry, last incident pointer) to <log_dir>/fleet/proc_<i>.jsonl
    # at the existing log boundary — zero extra device syncs (savlint
    # SAV112) — and process 0 writes the merged fleet manifest
    # (fleet/fleet.json: step skew, straggler ranking, dead-host
    # suspicion) at the end of fit. Requires a log_dir/checkpoint_dir
    # sink; render with tools/fleet_status.py or run_report.py --fleet.
    fleet: bool = True
    # Anomaly-triggered profiling (sav_tpu.obs.autoprof; docs/fleet.md):
    # when the goodput ledger flags a stall anomaly, a log window's
    # per-step time spikes past a robust median+MAD gate, or the
    # watchdog crosses its soft stage, arm jax.profiler for a bounded
    # autoprof_steps-step trace under <log_dir>/autoprof/, stamped into
    # the run manifest (notes.autoprof). Budgeted like the flight
    # recorder's incidents: at most autoprof_max captures per run.
    autoprof: bool = False
    autoprof_steps: int = 4
    autoprof_max: int = 2
    # Per-chip peak FLOP/s override for MFU/roofline accounting
    # (sav_tpu/obs/costs.py; train.py --peak-flops). None = resolve from
    # the device-kind table (an accelerator it does not list raises when
    # fit builds its cost observer); the CPU resolves to a deterministic
    # fake peak (labeled 'cpu-fake') so the attribution/MFU plumbing stays
    # assertable in tier-1. The peak is a denominator only: it chooses
    # nothing about how the step is compiled or dispatched.
    peak_flops: Optional[float] = None
    # Flight recorder (sav_tpu.obs.recorder; docs/incident_replay.md):
    # keep a bounded ring of the last record_depth steps' host-side
    # context (batch content hash + shapes/dtypes, rng recipe, logged
    # metrics) plus the raw host batches of the newest record_batches
    # steps and a periodic pre-step TrainState snapshot every
    # record_snapshot_every steps (None = record_batches). On an incident
    # — nonfinite logged metrics, a loss spike beyond spike_sigma scaled
    # MADs, a watchdog hang, or an uncaught exception — fit() dumps a
    # replayable bundle under <log_dir>/incidents/step_<N>/ for
    # tools/replay_step.py. Steady-state cost is host-only bookkeeping
    # (no extra device syncs; savlint SAV111 enforces); the periodic
    # snapshot is the one pipeline drain recording adds.
    record: bool = False
    record_depth: int = 16
    record_batches: int = 4
    record_snapshot_every: Optional[int] = None
    # Loss-spike incident gate: flag a logged loss more than spike_sigma
    # scaled MADs above the rolling median of healthy windows (upward
    # only; 0 disables). Armed after 8 healthy windows so early-training
    # noise cannot false-fire.
    spike_sigma: float = 6.0
    # Memory forensics (sav_tpu.obs.memdump; docs/profiling.md): on an
    # oom-classified exception, dump an incident bundle under
    # <log_dir>/incidents/memdump_<step>/ — live-buffer ranking
    # classified against the training state, HBM snapshot + watermark,
    # per-group parameter-byte estimates, and a device-memory pprof
    # where the backend supports one. Steady-state cost is a host-side
    # memory_stats() counter read per log boundary (the HBM watermark,
    # stamped into the manifest on every exit path regardless of this
    # knob). On by default: forensics only run when the run is already
    # dead.
    memdump: bool = True
    # Runtime sanitizer (sav_tpu.analysis.sanitize;
    # docs/static_analysis.md): after the first completed step, arm
    # jax.transfer_guard_host_to_device("disallow") on the training
    # thread (an implicit host->device transfer in the hot loop raises —
    # the feeder's explicit device_puts on its own thread are exempt).
    sanitize: bool = False

    @property
    def steps_per_epoch(self) -> int:
        return self.num_train_images // self.global_batch_size

    @property
    def total_steps(self) -> int:
        return self.steps_per_epoch * self.num_epochs

    @property
    def learning_rate(self) -> float:
        return self.base_lr * self.global_batch_size / self.lr_scaling_divisor

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, text: str) -> "TrainConfig":
        return cls(**json.loads(text))
