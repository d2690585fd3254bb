"""AOT-compiled serving engine: bucketed dynamic batching over warm
executables.

The inference product the training stack feeds (ROADMAP item 2). One
engine owns:

- **A bucket ladder of AOT executables.** Startup lowers + compiles one
  inference executable per (model, bucket batch size) — request time
  never traces or compiles. Where the persistent XLA cache is on
  (:mod:`sav_tpu.utils.compile_cache`: always on a TPU, on the CPU when
  a directory was given) a restart re-reads the compiles from
  disk in milliseconds, and :attr:`startup_report` counts cache hits vs
  from-scratch compiles so the warm path is assertable, not assumed.
- **A deadline-aware dynamic batcher** (:mod:`sav_tpu.serve.batcher`):
  bounded admission, batches formed into the largest bucket that fills
  before the earliest admitted deadline's slack expires, short batches
  padded to the bucket with a validity mask.
- **Host->device overlap**: batch N+1 is padded and placed on device by
  a :class:`~sav_tpu.data.feeder.DeviceFeeder` worker while the device
  executes batch N — the training input path's double-buffering rebased
  onto serving (place of N+1 strictly overlaps execution of N;
  tests/test_serve.py pins the ordering the same way
  tests/test_feeder.py does).
- **A latency ledger + run manifest**: p50/p95/p99 latency, throughput,
  queue depth, bucket occupancy, and padding waste finalize into a
  :class:`~sav_tpu.obs.manifest.RunManifest` so
  ``tools/regression_sentinel.py`` gates serving perf exactly like
  training perf (docs/serving.md).

Params restore **params-only** from any training checkpoint
(:meth:`sav_tpu.train.checkpoint.Checkpointer.restore_params_only` —
opt_state is never read, so serving HBM never holds optimizer moments),
and the model builds under the same tuned attention dispatch as
training (``attention_tune_cache`` winners apply at serving shapes too).

The wire format is uint8 end to end: requests carry
``[image_size, image_size, 3]`` uint8 rows
(:func:`sav_tpu.serve.preprocess.preprocess_request` shapes raw decoded
images), and the compiled program normalizes on device with the same op
the training ``device_preprocess`` path uses.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sav_tpu.serve.batcher import (
    DynamicBatcher,
    FormedBatch,
    QueueFullError,
    ServeClosedError,
)
from sav_tpu.serve.bucketing import BucketLadder, default_ladder
from sav_tpu.serve.latency import LatencyLedger
from sav_tpu.serve.telemetry import ServeTelemetry, stamp


@dataclasses.dataclass
class ServeConfig:
    """Serving configuration (the inference twin of TrainConfig)."""

    model_name: str = "deit_s_patch16"
    num_classes: int = 1000
    image_size: int = 224
    compute_dtype: str = "bfloat16"
    # None = the measured three-way auto dispatch (sav_tpu/ops/attention.py);
    # the attn_tune cache's winners apply at serving shapes too.
    attention_backend: Optional[str] = None
    attention_tune_cache: Optional[str] = None
    model_overrides: Optional[dict] = None
    # Batch-size rungs, one AOT executable each. None = powers of two up
    # to max_batch (sav_tpu/serve/bucketing.py).
    buckets: Optional[list] = None
    max_batch: int = 8
    # Admission bound: submits past this many queued requests are
    # rejected (QueueFullError) instead of growing the latency tail.
    max_queue: int = 256
    # Default per-request latency budget; the batcher ships a batch no
    # later than deadline - est_step(bucket) (docs/serving.md).
    deadline_ms: float = 100.0
    # Placed batches buffered beyond the one executing (DeviceFeeder
    # depth — host->device transfer of batch N+1 overlaps execution of N).
    feed_depth: int = 2
    # Training checkpoint to serve (params-only restore; opt_state is
    # never materialized). None = fresh init (benches, smoke tests).
    checkpoint_dir: Optional[str] = None
    # Serve int8 quantized weights (docs/quantization.md): the float
    # (checkpoint-format) param tree converts through
    # sav_tpu.ops.quant.quantize_params into int8 kernels + per-channel
    # f32 scales, and every projection/FFN/head dot runs the int8 MXU
    # pipe (the attention core stays in compute_dtype). Param HBM is
    # ~half the bf16 arm's (startup_report["quant"] proves it); logits
    # track the bf16 arm within the pinned tolerance
    # (tests/test_quant.py parity gates). Works with any float source —
    # a --quant QAT checkpoint (matching train/serve numerics) or a
    # plain bf16 one (post-training quantization).
    quant_weights: bool = False
    # Declarative sharding layout (sav_tpu/parallel/layout.py): a
    # built-in name ('tpN' | '2dXxY' | ...) or a tools/mesh_tune.py
    # preset path. The engine then builds its mesh from the layout and
    # SHARDS THE SERVING PARAMS by the layout's specs — one big model
    # spans chips via TP instead of replicating (the ROADMAP item-3
    # prerequisite). None keeps the single-device default (replicate
    # engines for more chips).
    layout_preset: Optional[str] = None
    # Override of the persistent compile cache's default directory
    # (loses to JAX_COMPILATION_CACHE_DIR — sav_tpu/utils/compile_cache.py).
    # With the cache on, a warm second start compiles nothing from scratch
    # (startup_report["compiled_from_scratch"] == 0).
    compilation_cache_dir: Optional[str] = None
    # Sink for the serving run manifest (None disables).
    log_dir: Optional[str] = None
    seed: int = 0
    # ---- serve telemetry (sav_tpu/serve/telemetry.py; docs/serving.md).
    # Per-request span tracing + live windowed metrics + SLO accounting
    # are in-memory even without a log_dir; heartbeats / slow-request
    # exemplars / anomaly captures need log_dir to land anywhere.
    telemetry: bool = True
    # Trailing window for the live p50/p99/throughput/queue view.
    telemetry_window_s: float = 30.0
    # Serve heartbeat cadence (kind=serve lines in fleet/proc_<i>.jsonl;
    # 0 disables the thread).
    heartbeat_secs: float = 5.0
    # Golden-probe cadence (sav_tpu/serve/quality.py; docs/quality.md):
    # every probe_every_s seconds an idle engine runs the checked-in
    # probe batch through the normal admission path and fingerprints
    # the logits. 0 disables the probe thread. Probes shed themselves
    # whenever live work is queued or in flight — they never evict a
    # live request.
    probe_every_s: float = 0.0
    # Completed request traces kept in the span ring.
    trace_ring: int = 256
    # Slow-request exemplar bundles dumped per run (serve_traces/).
    slow_exemplars: int = 8
    # Slow gate: latency beyond median + slow_sigma scaled MADs of the
    # live window flags a request as a slow exemplar (and arms the
    # anomaly profiler).
    slow_sigma: float = 4.0
    # SLO: deadline-hit-rate objective + Google-SRE two-window burn
    # alerting (docs/serving.md "SLO knobs").
    slo_target: float = 0.99
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 600.0
    slo_burn_threshold: float = 2.0
    # Anomaly-triggered bounded profiling (PR-7 AutoProfiler budget
    # machinery; trace window counted in completed batches).
    autoprof: bool = True
    autoprof_batches: int = 4
    autoprof_max: int = 2

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, text: str) -> "ServeConfig":
        return cls(**json.loads(text))

    def ladder(self) -> BucketLadder:
        return BucketLadder(
            self.buckets if self.buckets else default_ladder(self.max_batch)
        )


def build_infer_fn(model, compute_dtype) -> Callable:
    """The serving step: uint8 batch -> masked f32 logits.

    Shared by :class:`ServeEngine` and the zoo ``--serve`` check
    (tools/zoo_tpu_check.py) so "servable" means exactly one program
    shape. Normalization runs on device
    (:func:`sav_tpu.ops.preprocess.normalize_images` — the same op the
    training ``device_preprocess`` path uses, so serve and train see
    identical numerics from the same uint8 wire bytes); padded rows are
    zeroed by the validity mask so the contract "padding never leaks
    into results" is visible in the program itself.
    """
    from sav_tpu.ops import preprocess as pp

    def infer(params, batch_stats, batch):
        images = batch["images"]
        if images.dtype != jnp.uint8:
            raise ValueError(
                f"serving wire format is uint8, got {images.dtype}; "
                "preprocess_request() keeps requests uint8 end to end"
            )
        x = pp.normalize_images(images, compute_dtype)
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        logits = model.apply(variables, x, is_training=False)
        return logits.astype(jnp.float32) * batch["valid"][:, None]

    return infer


class ServeEngine:
    """One model, one bucket ladder of warm executables, one batcher.

    Lifecycle: construction does all the heavy lifting (params restore,
    per-bucket AOT compile + warmup — :attr:`startup_report`);
    :meth:`start` opens admission and spins up the serving threads;
    :meth:`submit` returns a future per request; :meth:`stop` drains
    in-flight batches, fails still-queued requests, and finalizes the
    manifest. Context manager = start/stop.

    Test seams: ``place_hook`` fires on the feeder thread after batch
    placement is issued, ``execute_hook`` on the device loop before
    execution — the overlap-ordering proof instruments both (the
    tests/test_feeder.py technique).
    """

    def __init__(
        self,
        config: ServeConfig,
        *,
        model=None,
        params=None,
        batch_stats=None,
        mesh=None,
        manifest=None,
        place_hook: Optional[Callable[[FormedBatch], None]] = None,
        execute_hook: Optional[Callable[[FormedBatch], None]] = None,
        autoprof=None,
    ):
        self.config = config
        self.ladder = config.ladder()
        self.place_hook = place_hook
        self.execute_hook = execute_hook
        from sav_tpu.obs import compile_log
        from sav_tpu.utils.compile_cache import enable_persistent_cache

        # min_compile_time 0: jax's 1 s default floor is tuned for
        # training (don't litter the cache with trivial programs), but a
        # serving restart wants EVERY bucket executable back from disk —
        # a warm start must compile nothing from scratch.
        cache_dir = enable_persistent_cache(
            config.compilation_cache_dir, min_compile_time_secs=0.0
        )
        compile_log.listen()
        if config.attention_tune_cache:
            from sav_tpu.ops.attn_tuning import set_cache_path

            set_cache_path(config.attention_tune_cache)
        from sav_tpu.parallel.layout import (
            BoundLayout,
            layout_from_mesh,
            resolve_layout,
        )

        explicit_layout = resolve_layout(config.layout_preset)
        if explicit_layout is not None and -1 in dict(
            explicit_layout.mesh_axes
        ).values():
            # Serving pins wildcard axes to 1: a built-in name like
            # 'tp2' carries data=-1, and absorbing the host's spare
            # chips onto the data axis would both break the bucket
            # ladder's shard-divisibility (bucket 1 % data) and
            # contradict the serving default — one engine claims
            # exactly the chips its TP degree needs, replicate engines
            # for more. A preset that WANTS a data axis sizes it
            # explicitly.
            import dataclasses as _dc

            explicit_layout = _dc.replace(
                explicit_layout,
                mesh_axes=tuple(
                    (a, 1 if s == -1 else s)
                    for a, s in explicit_layout.mesh_axes
                ),
            )
        if mesh is None:
            if explicit_layout is not None:
                # Layout-stated mesh over exactly the chips it sizes: a
                # TP/2D layout spans chips with sharded params instead
                # of replicating.
                mesh = explicit_layout.create_mesh()
            else:
                # Serving default: one device per engine (replicate
                # engines for more chips). A multi-device mesh is
                # accepted when every bucket divides its batch axes
                # (validated below).
                from sav_tpu.parallel.mesh import create_mesh

                mesh = create_mesh({"data": 1}, devices=jax.devices()[:1])
        self.mesh = mesh
        self.layout = (
            explicit_layout if explicit_layout is not None
            else layout_from_mesh(mesh)
        )
        self._blayout = BoundLayout(self.layout, mesh)
        from sav_tpu.parallel.mesh import batch_axes

        baxes = batch_axes(mesh)
        shards = int(np.prod([mesh.shape[a] for a in baxes])) if baxes else 1
        bad = [b for b in self.ladder.buckets if b % shards]
        if bad:
            raise ValueError(
                f"buckets {bad} do not divide the mesh batch axes "
                f"({dict((a, mesh.shape[a]) for a in baxes)}); every "
                "bucket must shard evenly — adjust the ladder or serve "
                "on a single-device mesh"
            )
        self._batch_sharding = self._blayout.batch_sharding()
        self.compute_dtype = (
            jnp.bfloat16 if config.compute_dtype == "bfloat16" else jnp.float32
        )
        # The dtype stamp telemetry/heartbeats/status tools render: what
        # the *weights* are served in (docs/quantization.md).
        self.serve_dtype = (
            "int8" if config.quant_weights
            else ("bf16" if config.compute_dtype == "bfloat16" else "f32")
        )
        t0 = time.perf_counter()
        self._restore_model = None
        if model is None:
            from sav_tpu.models import create_model

            model_kwargs = dict(
                num_classes=config.num_classes,
                dtype=self.compute_dtype,
                backend=config.attention_backend,
                # 2D-TP layouts pin between-block activations (the same
                # seam the trainer threads; 1D propagates from params).
                layout=(
                    self._blayout if self.layout.tp_feature_axis else None
                ),
                **(config.model_overrides or {}),
            )
            model = create_model(
                config.model_name,
                quant="int8_serve" if config.quant_weights else None,
                **model_kwargs,
            )
            if config.quant_weights:
                # The restore twin: the same architecture in float form.
                # Its param tree is what training checkpoints (and
                # passed-in trees) hold; the int8 serving tree is derived
                # from it by quantize_params below.
                self._restore_model = create_model(
                    config.model_name, quant=None, **model_kwargs
                )
        elif config.quant_weights:
            raise ValueError(
                "quant_weights=True builds its own int8_serve/float model "
                "pair from the registry; pass model=None (an externally "
                "built int8_serve model can be served directly — its "
                "params are already quantized, so quant_weights adds "
                "nothing)"
            )
        self.model = model
        if self._restore_model is None:
            self._restore_model = model
        self._params, self._batch_stats, params_source = self._load_params(
            params, batch_stats
        )
        noise_scale = os.environ.get("SAV_CHAOS_NOISE_WEIGHTS")
        if noise_scale:
            # Chaos seam (docs/quality.md "Chaos"): deterministically
            # corrupt the FLOAT tree before any quantization, so a
            # planted-fault replica misbehaves identically on every
            # arm — the shadow-agreement / probe-mismatch detection
            # tests and the r20 battery plant faults through this.
            from sav_tpu.serve.quality import noise_params

            self._params = noise_params(self._params, float(noise_scale))
        self._quant_report: Optional[dict] = None
        if config.quant_weights:
            self._params, self._quant_report = self._quantize_params_tree(
                self._params
            )
        # The serving program additionally returns per-row output
        # digests (top-1 / margin / entropy) computed in-graph — they
        # ride the existing result fetch, so quality telemetry costs
        # zero extra device syncs on the request path (SAV126;
        # docs/quality.md).
        from sav_tpu.serve.quality import digested_infer_fn

        self._infer = jax.jit(
            digested_infer_fn(build_infer_fn(model, self.compute_dtype))
        )
        # ---- AOT: one executable per bucket, warmed from the cache ----
        compile_t0 = time.perf_counter()
        self._executables: dict = {}
        from sav_tpu.ops.attention import partitioned_over

        # The buckets are traced here: 'auto' attention promotes a Mosaic
        # kernel only in a program of one device.
        with partitioned_over(self.mesh.size):
            for bucket in self.ladder.buckets:
                lowered = self._infer.lower(
                    self._params, self._batch_stats, self._abstract_batch(bucket)
                )
                self._executables[bucket] = lowered.compile()
        compile_s = time.perf_counter() - compile_t0
        # The loop's backend compiles by the cache's answer, from the
        # process's compile log.
        compiled = compile_log.summary(since=compile_t0)
        # Per-bucket executable HBM estimate (ride-along fix: the report
        # used to say nothing about how much device memory each rung
        # costs, so a ladder that barely fit was invisible until the
        # allocator said otherwise). XLA's own memory_analysis when the
        # backend provides one; an analytic floor (params + wire input +
        # f32 logits) otherwise — the source is recorded so a reader
        # knows which number they are trusting.
        self._param_bytes = sum(
            int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves((self._params, self._batch_stats))
        )
        bucket_hbm: dict = {}
        hbm_source = "analytic"
        s = config.image_size
        for bucket in self.ladder.buckets:
            est = None
            try:
                ma = self._executables[bucket].memory_analysis()
                est = int(
                    getattr(ma, "argument_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0)
                    + getattr(ma, "temp_size_in_bytes", 0)
                    + getattr(ma, "generated_code_size_in_bytes", 0)
                )
            except Exception:
                est = None
            if est:
                hbm_source = "memory_analysis"
            else:
                est = (
                    self._param_bytes
                    + bucket * s * s * 3
                    + bucket * config.num_classes * 4
                )
            bucket_hbm[str(bucket)] = est
        # Warmup: one execution per bucket seeds the batcher's per-bucket
        # step-time estimates (and faults in any lazy backend state).
        self._step_est: dict = {}
        warmup_t0 = time.perf_counter()
        for bucket in self.ladder.buckets:
            placed = self._place_host_batch(
                np.zeros(
                    (bucket, config.image_size, config.image_size, 3),
                    np.uint8,
                ),
                np.ones((bucket,), np.float32),
            )
            t = time.perf_counter()
            jax.block_until_ready(
                self._executables[bucket](
                    self._params, self._batch_stats, placed
                )
            )
            self._step_est[bucket] = time.perf_counter() - t
        self.startup_report = {
            "model": config.model_name,
            "layout": self.layout.name,
            "buckets": list(self.ladder.buckets),
            "params_source": params_source,
            "dtype": self.serve_dtype,
            "param_bytes": self._param_bytes,
            "bucket_hbm_bytes": bucket_hbm,
            "bucket_hbm_source": hbm_source,
            "startup_s": round(time.perf_counter() - t0, 3),
            "compile_s": round(compile_s, 3),
            "warmup_s": round(time.perf_counter() - warmup_t0, 3),
            "warmup_step_s": {
                str(b): round(s, 5) for b, s in self._step_est.items()
            },
            "cache_dir": cache_dir,
            # The warm-start proof: what the backend compiled in the AOT
            # loop (the cache had no answer, or is off) vs what it loaded.
            "compiled_from_scratch": (
                compiled["cache_misses"] + compiled["cache_off"]
            ),
            "cache_hits": compiled["cache_hits"],
        }
        if self._quant_report is not None:
            # The HBM-density proof: int8 serving bytes vs what the same
            # tree would weigh in bf16 (docs/quantization.md).
            self.startup_report["quant"] = self._quant_report
        self.manifest = manifest
        if self.manifest is None and config.log_dir:
            from sav_tpu.obs.manifest import RunManifest

            self.manifest = RunManifest(
                os.path.join(
                    config.log_dir,
                    f"manifest-serve-{time.strftime('%Y%m%d-%H%M%S')}"
                    f"-{os.getpid()}.json",
                ),
                kind="serve",
                config=dataclasses.asdict(config),
            )
            self.manifest.begin()
        if self.manifest is not None:
            self.manifest.note("serve_startup", self.startup_report)
            # Same provenance note the trainer stamps: "which layout was
            # this serving" reads from notes.layout alone.
            self.manifest.note("layout", self.layout.describe(self.mesh))
            if self._quant_report is not None:
                # notes.quant: "which arm was this" reads from here alone
                # (regression_sentinel keys int8 records off it).
                self.manifest.note(
                    "quant", dict(self._quant_report, weights="int8")
                )
        # ---- quality: digest windows + golden-probe ledger -------------
        # Always constructed (the digests ride every executable), even
        # without telemetry — tests and embedders can read
        # quality_snapshot() directly. Stdlib-side folds only
        # (sav_tpu/obs/quality.py); the probe thread spins up in
        # start() when probe_every_s > 0.
        from sav_tpu.obs.quality import ProbeLedger, QualityTracker

        self._quality = QualityTracker()
        self._probe_ledger = ProbeLedger()
        self._probe = None
        # ---- telemetry: spans + live windows + heartbeats + SLO --------
        self._telemetry: Optional[ServeTelemetry] = None
        self._watermark = None
        if config.telemetry:
            writer = None
            if config.log_dir and config.heartbeat_secs > 0:
                from sav_tpu.obs.fleet import (
                    HeartbeatWriter,
                    resolve_identity,
                )

                proc, procs = resolve_identity()
                writer = HeartbeatWriter(
                    config.log_dir,
                    process_index=proc,
                    process_count=procs,
                )
            if autoprof is None and config.autoprof and config.log_dir:
                from sav_tpu.obs.autoprof import AutoProfiler
                from sav_tpu.obs.fleet import resolve_identity

                autoprof = AutoProfiler(
                    config.log_dir,
                    trace_steps=config.autoprof_batches,
                    max_captures=config.autoprof_max,
                    process_index=resolve_identity()[0],
                    manifest=self.manifest,
                )
            from sav_tpu.obs.memdump import HbmWatermark

            self._watermark = HbmWatermark()

            def _hbm() -> Optional[dict]:
                self._watermark.observe()
                if not self._watermark.samples:
                    return None
                return {
                    "hbm_bytes_in_use": self._watermark.in_use_bytes,
                    "hbm_peak_bytes": self._watermark.peak_bytes,
                }

            self._telemetry = ServeTelemetry(
                config.log_dir,
                dtype=self.serve_dtype,
                trace_ring=config.trace_ring,
                exemplar_max=config.slow_exemplars,
                exemplar_sigma=config.slow_sigma,
                window_s=config.telemetry_window_s,
                heartbeat_secs=config.heartbeat_secs,
                slo_target=config.slo_target,
                slo_fast_window_s=config.slo_fast_window_s,
                slo_slow_window_s=config.slo_slow_window_s,
                slo_burn_threshold=config.slo_burn_threshold,
                writer=writer,
                autoprof=autoprof,
                queue_stats_fn=lambda: (
                    self._batcher.stats() if self._batcher else {}
                ),
                hbm_fn=_hbm,
                # Quality fields on every kind=serve beat (ISSUE 20):
                # digest drift gates + probe fingerprint state, folded
                # at beat cadence — never per request.
                quality_fn=self.quality_snapshot,
                # Measured capacity stamp (ISSUE 19): the ladder's top
                # rung over the windowed step — beats publish
                # capacity_rps, the fleet fold sums it into headroom.
                max_batch=self.ladder.max_batch,
            )
        self.ledger = LatencyLedger(
            window=(
                self._telemetry.window
                if self._telemetry is not None else None
            )
        )
        self._batcher: Optional[DynamicBatcher] = None
        self._feeder = None
        self._device_thread: Optional[threading.Thread] = None
        self._started = False
        self._stopped = False
        self._errors = 0

    # ------------------------------------------------------------ startup

    def _load_params(self, params, batch_stats) -> tuple:
        """(params, batch_stats, source): passed-in, params-only
        checkpoint restore, or fresh init — placed by the layout's param
        specs (replicated under the default DP layout; TP/2D layouts
        shard the serving weights over the mesh).

        Always the FLOAT (checkpoint-format) tree, built against
        ``self._restore_model`` — under ``quant_weights`` the caller
        converts it to the int8 serving tree afterwards
        (:meth:`_quantize_params_tree`), so every params source
        (checkpoint / passed / fresh init) quantizes identically."""
        if params is not None:
            def place(tree):
                if not tree:
                    return tree
                return jax.tree.map(
                    jax.device_put, tree, self._blayout.param_shardings(tree)
                )

            return place(params), place(batch_stats or {}), "passed"
        abstract = self._abstract_state()
        if self.config.checkpoint_dir:
            from sav_tpu.train.checkpoint import Checkpointer

            ckpt = Checkpointer(self.config.checkpoint_dir, read_only=True)
            try:
                restored = ckpt.restore_params_only(abstract)
            finally:
                ckpt.close()
            if restored is None:
                raise FileNotFoundError(
                    "no checkpoint found in "
                    f"{self.config.checkpoint_dir!r}"
                )
            return (
                restored["params"],
                restored.get("batch_stats") or {},
                f"checkpoint:{self.config.checkpoint_dir}",
            )
        # Fresh init (benches/smoke): jitted, materialized on the mesh
        # directly under the layout's shardings.
        rng = jax.random.PRNGKey(self.config.seed)
        s = self.config.image_size

        def init_fn(rng):
            dummy = jnp.zeros((1, s, s, 3), self.compute_dtype)
            variables = dict(
                self._restore_model.init(
                    {"params": rng}, dummy, is_training=False
                )
            )
            return {
                "params": variables.pop("params"),
                "batch_stats": variables.pop("batch_stats", {}),
            }

        out_shardings = self._blayout.param_shardings(
            jax.eval_shape(init_fn, rng)
        )
        built = jax.jit(init_fn, out_shardings=out_shardings)(rng)
        return built["params"], built["batch_stats"], "init"

    def _abstract_state(self) -> dict:
        """Abstract ``{"params", "batch_stats", "step"}`` template for the
        params-only restore (shapes from a traced init — no weights are
        materialized to build it), each leaf carrying its layout
        sharding so the restore materializes sharded."""
        rng = jax.random.PRNGKey(0)
        s = self.config.image_size

        def init_fn(rng):
            dummy = jnp.zeros((1, s, s, 3), self.compute_dtype)
            return dict(
                self._restore_model.init(
                    {"params": rng}, dummy, is_training=False
                )
            )

        shapes = jax.eval_shape(init_fn, rng)
        template = {
            "params": shapes["params"],
            "batch_stats": shapes.get("batch_stats", {}),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        shardings = self._blayout.param_shardings(template)
        return jax.tree.map(
            lambda sds, sh: jax.ShapeDtypeStruct(
                sds.shape, sds.dtype, sharding=sh
            ),
            template,
            shardings,
        )

    def _quantize_params_tree(self, float_params) -> tuple:
        """Float tree → the int8+scales serving tree, jitted with the
        layout's ``out_shardings`` so the int8 kernels materialize
        sharded exactly like their float twins (same tree paths — the
        SpecLayout rules key on names); the tiny ``scale`` leaves match
        no rule and replicate. Returns ``(quantized, report)`` where the
        report is the HBM-density proof: serving bytes vs the bf16
        weight of the same float tree."""
        from sav_tpu.ops.quant import quantize_params

        s = self.config.image_size

        def init_fn(rng):
            dummy = jnp.zeros((1, s, s, 3), self.compute_dtype)
            return dict(
                self.model.init({"params": rng}, dummy, is_training=False)
            )["params"]

        template = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        shardings = self._blayout.param_shardings(template)
        quantized = jax.jit(
            lambda p: quantize_params(p, template), out_shardings=shardings
        )(float_params)
        bf16_equiv = sum(
            int(leaf.size) * 2 for leaf in jax.tree.leaves(float_params)
        )
        serving = sum(
            int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(quantized)
        )
        report = {
            "weights_dtype": "int8",
            "param_bytes_serving": int(serving),
            "param_bytes_bf16_equiv": int(bf16_equiv),
            "param_bytes_ratio": round(serving / max(bf16_equiv, 1), 4),
        }
        return quantized, report

    def _abstract_batch(self, bucket: int) -> dict:
        s = self.config.image_size
        return {
            "images": jax.ShapeDtypeStruct(
                (bucket, s, s, 3), jnp.uint8, sharding=self._batch_sharding
            ),
            "valid": jax.ShapeDtypeStruct(
                (bucket,), jnp.float32, sharding=self._batch_sharding
            ),
        }

    # ------------------------------------------------------------ serving

    def start(self) -> "ServeEngine":
        if self._started:
            raise RuntimeError("engine already started")
        from sav_tpu.data.feeder import DeviceFeeder

        self._batcher = DynamicBatcher(
            self.ladder,
            step_time_fn=self._estimate_step,
            max_queue=self.config.max_queue,
            default_deadline_s=self.config.deadline_ms / 1e3,
        )
        self._feeder = DeviceFeeder(
            self._formed_batches(),
            self._place_formed,
            depth=self.config.feed_depth,
            name="serve-feeder",
        )
        self._device_thread = threading.Thread(
            target=self._device_loop, name="serve-device-loop", daemon=True
        )
        self._started = True
        self.ledger.start()
        if self._telemetry is not None:
            self._telemetry.start()
        self._device_thread.start()
        if self.config.probe_every_s > 0:
            from sav_tpu.serve.quality import ProbeRunner

            self._probe = ProbeRunner(
                self,
                self._probe_ledger,
                every_s=self.config.probe_every_s,
                log_dir=self.config.log_dir,
            ).start()
        return self

    def _estimate_step(self, bucket: int) -> float:
        """Per-bucket device seconds: warmup-seeded, EMA-updated from
        real batches (single writer: the device loop)."""
        return self._step_est.get(bucket, 0.0)

    def _formed_batches(self):
        """Batcher drain as the feeder's source iterator (runs on the
        feeder worker thread — the drain wait and the device_put of the
        next batch both overlap the device loop's execution)."""
        while True:
            formed = self._batcher.next_batch()
            if formed is None:
                return
            yield formed

    def _place_host_batch(self, images: np.ndarray, valid: np.ndarray) -> dict:
        return {
            "images": jax.device_put(images, self._batch_sharding),
            "valid": jax.device_put(valid, self._batch_sharding),
        }

    def _place_formed(self, formed: FormedBatch):
        """Pad to the bucket + issue the sharded device_put (feeder
        worker thread — this is the host->device stage that overlaps
        batch N's execution)."""
        try:
            s = self.config.image_size
            n = len(formed.requests)
            images = np.zeros((formed.bucket, s, s, 3), np.uint8)
            for i, request in enumerate(formed.requests):
                images[i] = request.payload
            valid = np.zeros((formed.bucket,), np.float32)
            valid[:n] = 1.0
            placed = self._place_host_batch(images, valid)
            if self._telemetry is not None:
                t_placed = self._telemetry.clock()
                for request in formed.requests:
                    stamp(request.trace, "placed", t_placed)
            if self.place_hook is not None:
                self.place_hook(formed)
            return formed, placed
        except BaseException as e:
            # A failed placement must not strand its submitters on
            # never-resolving futures; fail them, then let the feeder
            # propagate the error to the device loop.
            self._batcher.mark_completed()
            for request in formed.requests:
                if not request.future.done():
                    request.future.set_exception(e)
            raise

    def _device_loop(self):
        """Consume placed batches, execute, distribute results. The ONE
        device sync per batch (``np.asarray`` on the logits) lives here —
        after execution, outside the batcher drain (savlint SAV115)."""
        try:
            for formed, placed in self._feeder:
                t0 = time.perf_counter()
                try:
                    if self._telemetry is not None:
                        t_dispatch = self._telemetry.clock()
                        for request in formed.requests:
                            stamp(request.trace, "dispatched", t_dispatch)
                    if self.execute_hook is not None:
                        # After the dispatched stamp: a hook that holds
                        # the batch "on device" (the overlap/anomaly
                        # tests) books as device time, not dispatch wait.
                        self.execute_hook(formed)
                    out = self._executables[formed.bucket](
                        self._params, self._batch_stats, placed
                    )
                    # One fetch for the whole output tree: the logits
                    # plus the in-graph digest leaves land in the same
                    # transfer the logits alone used to (SAV126's
                    # zero-extra-syncs contract).
                    host = jax.device_get(out)
                    if self._telemetry is not None:
                        t_exec = self._telemetry.clock()
                        for request in formed.requests:
                            stamp(request.trace, "executed", t_exec)
                    self._complete(formed, host, t0)
                except Exception as e:  # noqa: BLE001 — fail batch, serve on
                    self._errors += 1
                    self._batcher.mark_completed()
                    for request in formed.requests:
                        if not request.future.done():
                            request.future.set_exception(e)
        except Exception:  # noqa: BLE001 — feeder/placement died
            # _place_formed already failed the in-flight batch's futures;
            # close() fails everything still queued, so no submitter is
            # left blocked on a future nothing will resolve.
            self._errors += 1
            if self._batcher is not None:
                self._batcher.close()

    def _complete(self, formed: FormedBatch, host: dict, t0: float):
        self._batcher.mark_completed()
        done_t = time.perf_counter()
        step_s = done_t - t0
        logits = host["logits"]
        # EMA keeps the batcher's dispatch-by estimate tracking the
        # hardware (warmup seeds it; single writer: this thread).
        prev = self._step_est.get(formed.bucket, step_s)
        self._step_est[formed.bucket] = 0.8 * prev + 0.2 * step_s
        now = time.monotonic()
        telemetry = self._telemetry
        latencies, overruns = [], []
        for i, request in enumerate(formed.requests):
            if telemetry is not None:
                stamp(request.trace, "depadded", telemetry.clock())
            request.future.set_result(logits[i])
            if telemetry is not None:
                stamp(request.trace, "completed", telemetry.clock())
            latencies.append(now - request.enqueue_t)
            overruns.append(now - request.deadline_t)
        n = len(formed.requests)
        # Digest rows into the quality window: host values, bounded
        # deque appends only — the gate math waits for the beat thread
        # (obs/quality.py; SAV126).
        self._quality.observe_digests(
            host["top1"][:n].tolist(),
            host["margin"][:n].tolist(),
            host["entropy"][:n].tolist(),
            num_classes=self.config.num_classes,
        )
        self.ledger.observe_batch(
            bucket=formed.bucket,
            latencies_s=latencies,
            overruns_s=overruns,
            queue_depth=formed.queue_depth,
            step_s=step_s,
        )
        if telemetry is not None:
            # Ring + SLO + the slow-exemplar/anomaly gates — host
            # bookkeeping on the window the ledger just fed (SAV116).
            telemetry.observe_completed(
                formed,
                latencies_s=latencies,
                overruns_s=overruns,
                step_s=step_s,
            )

    def submit(
        self,
        image: np.ndarray,
        *,
        deadline_ms: Optional[float] = None,
        trace_id=None,
    ):
        """Admit one preprocessed uint8 request; returns its future.

        ``image`` must be ``[image_size, image_size, 3]`` uint8 (use
        :func:`sav_tpu.serve.preprocess.preprocess_request` /
        :meth:`submit_raw` for raw decoded images). Raises
        :class:`~sav_tpu.serve.batcher.QueueFullError` on an admission
        reject (counted on the ledger).

        ``trace_id`` (ISSUE 16): a router-propagated fleet trace id —
        ``begin_trace`` ADOPTS it instead of minting a replica-local
        one, so this replica's spans join the fleet-wide trace.
        Replica-local serving (no id) is unchanged.
        """
        if not self._started or self._stopped:
            raise ServeClosedError("engine is not serving (start() first)")
        image = np.asarray(image)  # savlint: disable=SAV115 -- request validation on the submitted HOST image; no device value is in reach here
        s = self.config.image_size
        if image.shape != (s, s, 3) or image.dtype != np.uint8:
            raise ValueError(
                f"expected a [{s}, {s}, 3] uint8 request, got "
                f"{image.shape} {image.dtype}; run preprocess_request() "
                "(or submit_raw) first"
            )
        deadline_s = (
            deadline_ms / 1e3 if deadline_ms is not None
            else self.config.deadline_ms / 1e3
        )
        trace = (
            self._telemetry.begin_trace(deadline_s, rid=trace_id)
            if self._telemetry is not None else None
        )
        try:
            return self._batcher.submit(
                image,
                deadline_s=deadline_s,
                trace=trace,
            )
        except QueueFullError:
            self.ledger.observe_rejected()
            if self._telemetry is not None:
                self._telemetry.observe_shed()
            raise

    def submit_raw(
        self, image: np.ndarray, *, deadline_ms: Optional[float] = None
    ):
        """``submit`` for raw decoded images: center-crop + bicubic
        resize on the host (uint8 in, uint8 out), then admit."""
        from sav_tpu.serve.preprocess import preprocess_request

        return self.submit(
            preprocess_request(image, self.config.image_size),
            deadline_ms=deadline_ms,
        )

    # ----------------------------------------------------------- shutdown

    def drain(self, timeout_s: float = 30.0, *, poll_s: float = 0.02) -> bool:
        """Wait until every ACCEPTED request has resolved (queue empty,
        no drained batch still on the device loop) — the graceful half
        of leaving a fleet: a replica told to go away (SIGTERM from the
        pool, a weight swap) stops ADMITTING first (its server closes
        the listener), drains here, then :meth:`stop`s — nothing it
        accepted is failed by its own shutdown. Returns True when fully
        drained, False on timeout (stop() then fails the stragglers
        loudly). Host-side polling only — no device sync beyond the
        device loop's own."""
        if self._batcher is None:
            return True
        deadline = time.monotonic() + float(timeout_s)
        while self._batcher.pending() > 0:
            if time.monotonic() >= deadline:
                return False
            time.sleep(poll_s)
        return True

    def stop(
        self,
        timeout_s: float = 30.0,
        *,
        error: Optional[BaseException] = None,
    ) -> dict:
        """Drain in-flight batches, fail queued requests, finalize the
        manifest. Returns the final serving summary. Idempotent.

        ``error`` is the exception the caller is unwinding on (the
        context manager passes it through): the manifest then finalizes
        with that exception's outcome, NOT ``ok`` — a run whose driver
        died mid-serve must never enter the sentinel history as a
        healthy serving baseline built from the few requests that
        happened to finish (finalize is first-wins, so a later error
        finalize by the caller would be a no-op).
        """
        if self._stopped:
            return self.ledger.summary()
        self._stopped = True
        if self._probe is not None:
            # Before the batcher closes: the probe thread must not be
            # mid-submit when admission shuts, and its ledger state must
            # be final before telemetry's close() emits the final
            # quality beat (the leave-the-failing-fingerprint-on-disk
            # contract, docs/quality.md).
            self._probe.close()
        if self._batcher is not None:
            self._batcher.close()
        if self._device_thread is not None:
            self._device_thread.join(timeout=timeout_s)
        if self._feeder is not None:
            self._feeder.close()
        summary = self.ledger.summary()
        if error is not None:
            from sav_tpu.obs.manifest import classify_exception

            outcome, detail = classify_exception(error), repr(error)
        elif self._errors:
            outcome, detail = "error", f"{self._errors} batch(es) failed"
        else:
            outcome, detail = "ok", None
        tele_summary = None
        if self._telemetry is not None:
            if self._watermark is not None:
                try:
                    self._watermark.finalize()
                except Exception:
                    pass
            tele_summary = self._telemetry.close(outcome)
        if self.manifest is not None:
            metrics = self.ledger.flat_metrics()
            if self.config.quant_weights:
                # Flat marker so run records are filterable by arm even
                # when the notes were stripped (sentinel isolation).
                metrics["serve/quant_weights"] = 1.0
            if self.startup_report.get("compiled_from_scratch") is not None:
                metrics["serve/compiled_from_scratch"] = float(
                    self.startup_report["compiled_from_scratch"]
                )
            self.manifest.note("serve_summary", summary)
            if tele_summary is not None:
                slo = tele_summary.get("slo") or {}
                # SLO facts flow manifest -> normalize_run_record ->
                # sentinel (slo_hit_frac higher-better); absent on
                # zero-request runs — skipped, never zero-filled.
                if isinstance(slo.get("hit_frac"), (int, float)):
                    metrics["serve/slo_hit_frac"] = float(slo["hit_frac"])
                if isinstance(slo.get("burn_rate"), (int, float)):
                    metrics["serve/burn_rate"] = float(slo["burn_rate"])
                metrics["serve/shed"] = float(tele_summary.get("shed", 0))
                self.manifest.note("serve_telemetry", {
                    "slo": slo,
                    "window": tele_summary.get("window"),
                    "exemplars": tele_summary.get("exemplars"),
                    "heartbeats": tele_summary.get("heartbeats"),
                    "traced": tele_summary.get("traced"),
                    "overhead_s": tele_summary.get("overhead_s"),
                    "autoprof": tele_summary.get("autoprof"),
                })
                if tele_summary.get("alerts"):
                    # notes.alerts: which rules fired and how many
                    # episodes — "what paged during this run" reads
                    # from the manifest alone (ISSUE 19).
                    self.manifest.note(
                        "alerts", tele_summary["alerts"]
                    )
            qsnap = self.quality_snapshot()
            if qsnap.get("n") or qsnap.get("probe_runs"):
                # notes.quality + the sentinel-facing probe metric:
                # "what did this run predict and did the probe hold"
                # reads from the manifest alone. probe_ok_frac is
                # absent when no probe ran — skipped, never
                # zero-filled (the attention_core_frac contract).
                self.manifest.note("quality", qsnap)
                if isinstance(qsnap.get("probe_ok_frac"), (int, float)):
                    metrics["serve/probe_ok_frac"] = float(
                        qsnap["probe_ok_frac"]
                    )
            if (
                self._watermark is not None
                and self._watermark.source is not None
            ):
                # source "device-stats" on accelerators; finalize()'s
                # "live-arrays" backfill keeps the field present on CPU.
                metrics["serve/hbm_peak_bytes"] = float(
                    self._watermark.peak_bytes
                )
            self.manifest.finalize(outcome, error=detail, metrics=metrics)
        return summary

    def __enter__(self) -> "ServeEngine":
        return self.start() if not self._started else self

    def __exit__(self, exc_type, exc, tb):
        self.stop(error=exc)
        return False

    def quality_snapshot(self) -> dict:
        """The quality fields one heartbeat (and the manifest's
        ``notes.quality``) carries: digest drift gates + probe ledger
        state. Host bookkeeping only — named for savlint SAV126's
        audit set, which proves no device sync ever hides in here."""
        out = self._quality.snapshot()
        out.update(self._probe_ledger.snapshot())
        return out

    def stats(self) -> dict:
        out = {"ledger": self.ledger.summary(), "errors": self._errors}
        qsnap = self.quality_snapshot()
        if qsnap.get("n") or qsnap.get("probe_runs"):
            out["quality"] = qsnap
        if self._batcher is not None:
            out["batcher"] = self._batcher.stats()
        if self._feeder is not None:
            out["feeder"] = self._feeder.stats()
        if self._telemetry is not None:
            # The live mid-run view: windowed percentiles (None before
            # the first completed batch — never an exception) + SLO burn.
            out["live"] = self.ledger.live()
            out["slo"] = self._telemetry.slo.state()
            out["telemetry"] = self._telemetry.stats()
        return out
