"""pjit SPMD trainer.

The TPU-native replacement for both reference trainers (the pmap click CLI,
/root/reference/train.py:191-255, and the jaxline Experiment,
experiments/base.py:30-239): one jitted train step over a
``jax.sharding.Mesh``. There are no hand-written ``psum``/``pmean`` calls —
the batch is sharded over the ``data`` axis, parameters are replicated (or
TP-sharded via :mod:`sav_tpu.parallel.sharding` rules), and XLA's
partitioner emits the gradient AllReduce over ICI/DCN. One trainer covers
both stateless and BatchNorm models (collapsing base.py/base_with_state.py),
state is donated for in-place buffer reuse (base.py:64-68), logging happens
on the host outside the compiled step (fixing train.py:102-107's
wandb-inside-pmap tracer leak), and restore actually works (train.py never
called it).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from collections import deque
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sav_tpu.models import create_model
from sav_tpu.models.registry import model_task
from sav_tpu.obs import compile_log
from sav_tpu.obs.diagnostics import diagnostics_metrics
from sav_tpu.obs.goodput import GoodputLedger
from sav_tpu.obs.spans import SpanTracer, in_phase
from sav_tpu.ops.attention import partitioned_over
from sav_tpu.parallel.layout import (
    BoundLayout,
    layout_from_mesh,
    resolve_layout,
)
from sav_tpu.parallel.mesh import batch_axes, create_mesh
from sav_tpu.train.checkpoint import Checkpointer
from sav_tpu.train.config import TrainConfig
from sav_tpu.train.optimizer import (
    EmaState,
    ema_params,
    make_optimizer,
    warmup_cosine_schedule,
)
from sav_tpu.train.state import TrainState
from sav_tpu.train.tasks import make_task
from sav_tpu.utils import profiler
from sav_tpu.utils.debug import assert_all_finite


class Trainer:
    @in_phase("trainer/init")
    def __init__(
        self,
        config: TrainConfig,
        *,
        mesh=None,
        model=None,
        layout=None,
        checkpointer: Optional[Checkpointer] = None,
    ):
        self.config = config
        # Before any jit dispatch, so this trainer's own compiles are
        # covered. Placement is compile_cache's one rule: the
        # JAX_COMPILATION_CACHE_DIR variable, then this override, then the
        # fixed in-checkout directory on a TPU (off on the CPU).
        from sav_tpu.utils.compile_cache import enable_persistent_cache

        self.compile_cache_dir = enable_persistent_cache(
            config.compilation_cache_dir
        )
        compile_log.listen()
        if config.attention_tune_cache:
            # Trace-time-only process state: the 'auto' dispatcher reads
            # the shape→config table while tracing (sav_tpu/ops/
            # attn_tuning.py); no jitted path ever consults it.
            from sav_tpu.ops.attn_tuning import set_cache_path

            set_cache_path(config.attention_tune_cache)
        # Declarative layout (sav_tpu/parallel/layout.py): an explicit
        # layout object or config.layout_preset states the mesh AND every
        # param/activation spec; otherwise the layout is inferred from
        # mesh_axes (exactly the pre-layout rule selection, so existing
        # configs behave identically). ONE source of truth: a preset
        # composing with an explicit mesh_axes would be two, so it is
        # rejected, and an explicit mesh must satisfy the layout.
        explicit_layout = (
            layout if layout is not None
            else resolve_layout(config.layout_preset)
        )
        if explicit_layout is not None and config.mesh_axes:
            raise ValueError(
                "config.layout_preset / Trainer(layout=...) and "
                "config.mesh_axes are two sources of layout truth; set "
                "one (the layout states its own mesh axes)"
            )
        if mesh is not None:
            self.mesh = mesh
        elif explicit_layout is not None:
            self.mesh = explicit_layout.create_mesh()
        else:
            self.mesh = create_mesh(config.mesh_axes)
        self.layout = (
            explicit_layout if explicit_layout is not None
            else layout_from_mesh(self.mesh)
        )
        # Raises on axis/size mismatch between an explicit layout and an
        # explicit mesh; binds the specs for the placements below.
        self._blayout = BoundLayout(self.layout, self.mesh)
        self.compute_dtype = (
            jnp.bfloat16 if config.compute_dtype == "bfloat16" else jnp.float32
        )
        # The model-facing part of the step (sav_tpu/train/tasks.py): the
        # registry entry names it; an externally built model may carry a
        # ``task`` attribute, else it classifies images.
        self.task = make_task(
            model_task(config.model_name) if model is None
            else getattr(model, "task", "image"),
            config, self.compute_dtype,
        )
        # The softmax dtype is a *model attribute*, not process state:
        # attention blocks resolve ``logits_dtype or dtype`` themselves, so
        # two trainers with different settings coexist structurally (no
        # re-pinning around lazy traces). None inherits the compute dtype —
        # exactly the reference's semantics (its logits einsum runs in the
        # model dtype, attention.py:41-48, so a bf16 reference run has bf16
        # logits). Accuracy-gated both ways (tools/logits_dtype_gate.py:
        # identical final top-1 under f32 and bf16 compute) and measured
        # −15% step time on v5e (PERF.md §6). Force 'float32' for f32
        # softmax under bf16 compute. An externally passed ``model``
        # carries its own attributes; config.attention_logits_dtype does
        # not apply to it.
        if config.sequence_parallel:
            from sav_tpu.parallel.mesh import SEQ_AXIS

            if SEQ_AXIS not in self.mesh.axis_names:
                raise ValueError(
                    f"sequence_parallel={config.sequence_parallel!r} needs a "
                    f"'{SEQ_AXIS}' mesh axis; got {self.mesh.axis_names} "
                    "(set mesh_axes={'data': -1, 'seq': N} or train.py --sp N)"
                )
        pp = config.pipeline_parallel
        if pp is not None and pp > 1 and model is None:
            if config.sequence_parallel:
                raise ValueError(
                    "pipeline_parallel does not compose with "
                    "sequence_parallel (the pipelined stages run the dense "
                    "attention core); pick one"
                )
            if config.quant is not None:
                raise ValueError(
                    "pipeline_parallel does not compose with the int8 quant "
                    "arm yet (the pipelined stage wrappers do not thread the "
                    "'quant' field); drop --quant or --pp"
                )
            from sav_tpu.models.pipelined import create_pipelined_model

            self.model = create_pipelined_model(
                config.model_name,
                num_stages=pp,
                num_microbatches=config.pipeline_microbatches,
                mesh=self.mesh,
                num_classes=config.num_classes,
                dtype=self.compute_dtype,
                backend=config.attention_backend,
                logits_dtype=config.attention_logits_dtype,
                **(config.model_overrides or {}),
            )
        else:
            self.model = (
                model
                if model is not None
                else create_model(
                    config.model_name,
                    num_classes=config.num_classes,
                    dtype=self.compute_dtype,
                    backend=config.attention_backend,
                    logits_dtype=config.attention_logits_dtype,
                    # int8 QAT arm: projection/FFN dots via
                    # sav_tpu/ops/quant.py (attention core stays bf16).
                    quant=config.quant,
                    # SP threads the trainer's mesh into every attention
                    # block (the blocks shard_map q/k/v over its 'seq' axis).
                    seq_parallel=config.sequence_parallel,
                    seq_mesh=self.mesh if config.sequence_parallel else None,
                    # 2D-TP layouts thread the bound layout so encoder
                    # blocks pin activations to P(batch, None, 'y')
                    # between blocks; 1D TP propagates from the param
                    # specs alone, and SP's shard_map owns its own specs.
                    layout=(
                        self._blayout
                        if self.layout.tp_feature_axis
                        and not config.sequence_parallel
                        else None
                    ),
                    **(config.model_overrides or {}),
                )
            )
        if model is not None:
            # These config fields are model *attributes* now; an external
            # model carries its own. Silent divergence would train with
            # different softmax numerics / without SP than the config
            # says (the old process-global pinning DID apply them), so
            # mismatches fail loudly.
            def _canon(d):
                return None if d is None else jnp.dtype(d).name

            want = config.attention_logits_dtype
            have = getattr(model, "logits_dtype", None)
            if want is not None and _canon(have) != _canon(want):
                raise ValueError(
                    f"config.attention_logits_dtype={want!r} but the "
                    f"externally built model has logits_dtype={have!r}; "
                    "pass create_model(..., logits_dtype=...) to match, or "
                    "leave the config field None"
                )
            if config.quant is not None and (
                getattr(model, "quant", None) != config.quant
            ):
                raise ValueError(
                    f"config.quant={config.quant!r} but the externally "
                    "built model does not carry it; pass "
                    "create_model(..., quant=...) to match, or leave the "
                    "config field None"
                )
            if config.sequence_parallel is not None and (
                getattr(model, "seq_parallel", None) != config.sequence_parallel
            ):
                raise ValueError(
                    f"config.sequence_parallel={config.sequence_parallel!r} "
                    "but the externally built model does not carry it; pass "
                    "create_model(..., seq_parallel=..., seq_mesh=...) to "
                    "match, or leave the config field None"
                )
            if (config.pipeline_parallel or 1) > 1 and (
                getattr(model, "num_stages", None) != config.pipeline_parallel
            ):
                raise ValueError(
                    f"config.pipeline_parallel={config.pipeline_parallel} "
                    "but the externally built model is not a pipelined model "
                    "with that stage count; build it via "
                    "create_pipelined_model(...) or leave the field None"
                )
        self.schedule = warmup_cosine_schedule(
            config.learning_rate,
            steps_per_epoch=config.steps_per_epoch,
            warmup_epochs=config.warmup_epochs,
            num_epochs=config.num_epochs,
            end_lr=config.end_lr,
        )
        # The per-leaf chain on every mesh: one pass over each parameter.
        self.tx = make_optimizer(
            self.schedule,
            weight_decay=config.weight_decay,
            clip_grad_norm=config.clip_grad_norm,
            ema_decay=config.ema_decay,
        )
        self._train_step = jax.jit(self._train_step_impl, donate_argnums=(0,))
        self.checkpointer = checkpointer
        if checkpointer is None and config.checkpoint_dir:
            self.checkpointer = Checkpointer(
                config.checkpoint_dir, keep=config.checkpoint_keep
            )
        self._eval_step = jax.jit(self._eval_step_impl)
        # Goodput ledger summary of the most recent fit() (sav_tpu.obs).
        self.last_goodput: Optional[dict] = None

    # ------------------------------------------------------------------ init

    def _dummy_batch(self) -> int:
        # Batch sized to the mesh's batch-axes product: init traces the
        # model once, and under sequence parallelism a batch that does
        # not divide the data axes takes the replication fallback — the
        # replication warning once came from exactly this dummy (batch 2 vs
        # a data axis of 4 in the talking-heads SP leg), not from any
        # real training batch. Shape only: the zeros materialize inside
        # the jitted init_fn (traced, never a host buffer), so a 256-way
        # data axis does not cost a concrete global-batch-sized array.
        return max(
            2,
            int(np.prod([self.mesh.shape[a] for a in batch_axes(self.mesh)])),
        )

    @in_phase("trainer/init_state")
    def init_state(self, seed: Optional[int] = None) -> TrainState:
        """Build a sharded TrainState directly on the mesh.

        The state is created *inside* jit with explicit out_shardings, so
        large models materialize sharded — parameters never pass through a
        single host buffer.
        """
        rng = jax.random.PRNGKey(self.config.seed if seed is None else seed)
        dummy_batch = self._dummy_batch()

        def init_fn(rng):
            dummy = self.task.dummy_input(dummy_batch)
            variables = self.model.init({"params": rng}, dummy, is_training=False)
            variables = dict(variables)
            params = variables.pop("params")
            batch_stats = variables.pop("batch_stats", {})
            opt_state = self.tx.init(params)
            return TrainState.create(params, opt_state, batch_stats)

        abstract = jax.eval_shape(init_fn, rng)
        # Rules match on path *suffixes*, so optimizer-state mirrors of the
        # param tree (mu/nu) pick up the same TP shardings automatically.
        shardings = self._blayout.param_shardings(abstract)
        state = jax.jit(init_fn, out_shardings=shardings)(rng)
        return state

    def warm_start_from(self, directory: str) -> TrainState:
        """Fresh state (step 0, fresh optimizer) with params/batch_stats
        loaded from another run's checkpoint — the finetune path the
        reference lacked entirely (its restore was never wired,
        /root/reference/train.py:123-127, SURVEY.md §5).

        Cross-resolution transfers follow the standard ViT recipe
        (DeiT/CaiT 224-pretrain → 384-finetune): ``pos_embed`` tables are
        bicubic-resampled to the new token count
        (:mod:`sav_tpu.models.surgery`). Any other shape mismatch (e.g. a
        different-width head for a new label space) keeps the fresh
        initialization for that leaf, logged — classic warm-start
        semantics.
        """
        import logging

        from sav_tpu.models.surgery import adapt_pos_embeds

        source = Checkpointer(directory, read_only=True)
        try:
            raw = source.restore_raw()
        finally:
            source.close()
        if raw is None:
            raise FileNotFoundError(f"no checkpoint found in {directory!r}")
        src_params = raw["params"] if isinstance(raw, dict) else raw.params
        src_stats = (
            raw.get("batch_stats", {}) if isinstance(raw, dict)
            else raw.batch_stats
        )
        fresh = self.init_state()
        src_params = adapt_pos_embeds(src_params, fresh.params)
        counts = {"transferred": 0, "fresh": 0}

        def merge(tree_src, tree_fresh, collection):
            flat_src = {
                tuple(p): l
                for p, l in jax.tree_util.tree_flatten_with_path(tree_src)[0]
            }

            def pick(path, fresh_leaf):
                src = flat_src.get(tuple(path))
                name = "/".join(str(getattr(k, "key", k)) for k in path)
                if src is None or src.shape != fresh_leaf.shape:
                    # warning level: the default unconfigured logger drops
                    # info, and a silently-fresh "warm start" (e.g. wrong
                    # model_overrides failing every shape check) must be
                    # visible.
                    logging.warning(
                        "warm start: %s %s %s; keeping fresh init",
                        collection, name,
                        "not in source" if src is None
                        else f"shape {src.shape} != {fresh_leaf.shape}",
                    )
                    counts["fresh"] += 1
                    return fresh_leaf
                counts["transferred"] += 1
                return jax.device_put(
                    jnp.asarray(src, dtype=fresh_leaf.dtype),
                    fresh_leaf.sharding,
                )

            return jax.tree_util.tree_map_with_path(pick, tree_fresh)

        params = merge(src_params, fresh.params, "params")
        stats = (
            merge(src_stats, fresh.batch_stats, "batch_stats")
            if fresh.batch_stats else fresh.batch_stats
        )
        logging.warning(
            "warm start from %s: %d leaves transferred, %d fresh",
            directory, counts["transferred"], counts["fresh"],
        )
        # Reseed the parameter EMA (if configured) from the TRANSFERRED
        # weights: tx.init built it from the random init, and eval-on-EMA
        # would otherwise spend ~1/(1-decay) steps converging back from
        # garbage on exactly the short finetunes EMA is meant to help.
        # jnp.array(copy=True): the EMA leaf must be a DISTINCT buffer — a
        # no-copy device_put of the (already-f32, already-placed) param
        # leaf would alias it, and the donated train step then donates the
        # same buffer twice (runtime crash on the first finetune step).
        opt_state = jax.tree_util.tree_map(
            lambda s: (
                EmaState(
                    ema=jax.tree.map(
                        lambda e, p: jax.device_put(
                            jnp.array(p, dtype=e.dtype, copy=True), e.sharding
                        ),
                        s.ema,
                        params,
                    )
                )
                if isinstance(s, EmaState)
                else s
            ),
            fresh.opt_state,
            is_leaf=lambda x: isinstance(x, EmaState),
        )
        return fresh.replace(
            params=params, batch_stats=stats, opt_state=opt_state
        )

    def _check_checkpoint_layout(self) -> None:
        """Probe the saved opt-state layout (docs/elasticity.md): refuse
        flat Adam moments, which no optimizer of this tree builds, and say
        up front when ``config.ema_decay`` contradicts the checkpoint."""
        import logging

        layout = self.checkpointer.opt_layout()
        if layout.get("fused"):
            raise ValueError(
                f"the checkpoint in {self.checkpointer.directory!r} holds "
                "flat Adam moments (one vector a moment: fused_optimizer="
                "True, or auto on a data-parallel mesh before PR 29); this "
                "tree builds the per-leaf layout only. Commit e12e221 (PR "
                "41) is the last that reads the flat layout: resume there, "
                "or warm-start from the checkpoint's parameters "
                "(Trainer.warm_start_from)"
            )
        if layout.get("ema") is not None and bool(layout.get("ema")) != (
            self.config.ema_decay is not None
        ):
            logging.warning(
                "checkpoint %s a parameter-EMA tree but config.ema_decay "
                "is %s — restore will fail with a structure mismatch "
                "unless --ema-decay matches the checkpointed run",
                "carries" if layout.get("ema") else "lacks",
                self.config.ema_decay,
            )

    def restore_or_init(self) -> TrainState:
        if self.checkpointer is not None and self.checkpointer.latest_step() is not None:
            # Before the template is built: a layout that cannot be
            # restored is named here, not by orbax's structure error.
            self._check_checkpoint_layout()
        state = self.init_state()
        if self.checkpointer is not None:
            try:
                restored = self.checkpointer.restore_latest(state)
            except Exception as e:
                # Only attribute tree/structure mismatches to the EMA
                # knob; other failures (corrupt checkpoint, I/O errors)
                # re-raise untouched. Match the exception type AND an
                # anchored phrase — a bare substring would false-positive
                # on paths containing 'tree'.
                msg = str(e).lower()
                mismatch = isinstance(e, (ValueError, TypeError, KeyError)) and any(
                    phrase in msg
                    for phrase in ("tree structure", "pytree", "same structure")
                )
                if mismatch:
                    raise RuntimeError(
                        "checkpoint restore failed with a state-structure "
                        "mismatch; --ema-decay (TrainConfig.ema_decay) adds "
                        "an EMA tree to the opt-state and must match the "
                        "checkpoint — set it iff the checkpointed run had it"
                    ) from e
                raise
            if restored is not None:
                return restored
        return state

    # ----------------------------------------------------------------- steps

    def _train_step_impl(self, state: TrainState, batch: dict, rng: jax.Array):
        # Four named scopes (preprocess, loss, optimizer, metrics) name the
        # device time that no flax module does; metadata only. None goes
        # around model.apply: the module paths must read as they are.
        task = self.task
        step_rng = jax.random.fold_in(rng, state.step)
        with jax.named_scope("preprocess"):
            inputs, targets = task.prepare(batch, step_rng, training=True)
        has_bn = bool(state.batch_stats)

        def loss_fn(
            params, batch_stats, inputs, targets, dropout_rng, sd_rng,
            quant_rng=None,
        ):
            variables = {"params": params}
            if has_bn:
                variables["batch_stats"] = batch_stats
            rngs = {"dropout": dropout_rng, "stochastic_depth": sd_rng}
            if quant_rng is not None:
                # int8 QAT: stochastic rounding of the backward gradient
                # dots (sav_tpu/ops/quant.py); flax's make_rng folds the
                # module path in, so every quantized dot draws independent
                # rounding bits from this one stream.
                rngs["quant"] = quant_rng
            # 'losses' collects auxiliary objectives modules sow (e.g. the
            # MoE load-balancing loss); empty for most models.
            mutable = ["batch_stats", "losses"] if has_bn else ["losses"]
            # 'auto' attention resolves while the model is traced, and
            # promotes a Mosaic kernel only in a program of one device.
            with partitioned_over(self.mesh.size):
                outputs, new_vars = self.model.apply(
                    variables,
                    inputs,
                    is_training=True,
                    rngs=rngs,
                    mutable=mutable,
                    **task.apply_kwargs(targets),
                )
            new_batch_stats = new_vars["batch_stats"] if has_bn else batch_stats
            # Sown 'losses' are ready-to-sum penalties at their relative
            # scales (see MoEFFBlock's convention note); aux_loss_weight is
            # the single relative→loss-units conversion, and the logged
            # aux_loss metric is the relative-units sum.
            with jax.named_scope("loss"):
                aux = sum(
                    jnp.sum(leaf)
                    for leaf in jax.tree.leaves(new_vars.get("losses", {}))
                )
                aux = jnp.asarray(aux, jnp.float32)
                loss = (
                    task.loss(outputs, targets)
                    + self.config.aux_loss_weight * aux
                )
            return loss, (outputs, new_batch_stats, aux)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        accum = self.config.grad_accum_steps
        if accum < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {accum}")
        # The quant stream only exists on the int8 arm, and splits 3-way
        # instead of 2-way there — float runs keep their exact historical
        # dropout/stochastic-depth streams (pinned tests depend on them).
        quantized = self.config.quant is not None
        if accum == 1:
            if quantized:
                dropout_rng, sd_rng, quant_rng = jax.random.split(step_rng, 3)
            else:
                dropout_rng, sd_rng = jax.random.split(step_rng)
                quant_rng = None
            (loss, (outputs, new_batch_stats, aux_loss)), grads = grad_fn(
                state.params, state.batch_stats, inputs, targets,
                dropout_rng, sd_rng, quant_rng,
            )
        else:
            # Gradient accumulation: scan over micro-batches, averaging
            # grads/losses; one optimizer update. BatchNorm statistics
            # thread through the scan carry (each micro-batch sees the
            # previous micro-batch's running stats, like sequential steps).
            b = inputs.shape[0]
            if b % accum:
                raise ValueError(
                    f"batch size {b} not divisible by grad_accum_steps {accum}"
                )

            def split(x):
                return x.reshape(accum, b // accum, *x.shape[1:])

            def micro(carry, xs):
                bs, gsum, lsum, asum, i = carry
                im, lp = xs
                micro_rng = jax.random.fold_in(step_rng, i)
                if quantized:
                    dr, sr, qr = jax.random.split(micro_rng, 3)
                else:
                    dr, sr = jax.random.split(micro_rng)
                    qr = None
                (l, (lg, nbs, ax)), g = grad_fn(
                    state.params, bs, im, lp, dr, sr, qr
                )
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (nbs, gsum, lsum + l, asum + ax, i + 1), lg

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            carry0 = (
                state.batch_stats, zeros, jnp.float32(0.0), jnp.float32(0.0),
                jnp.int32(0),
            )
            (new_batch_stats, gsum, lsum, asum, _), outputs_stack = jax.lax.scan(
                micro, carry0, jax.tree.map(split, (inputs, targets))
            )
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
            aux_loss = asum / accum
            outputs = jax.tree.map(
                lambda x: x.reshape(b, *x.shape[2:]), outputs_stack
            )
        with jax.named_scope("optimizer"):
            updates, new_opt_state = self.tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=new_batch_stats,
        )
        with jax.named_scope("metrics"):
            metrics = {
                "loss": loss,
                **task.train_metrics(outputs, batch),
                "learning_rate": self.schedule(state.step),
                "grad_norm": optax.global_norm(grads),
                "aux_loss": aux_loss,
            }
            if self.config.diagnostics:
                # In-jit diagnostics (sav_tpu.obs.diagnostics): computed on
                # device, returned with the step metrics, so they ride the
                # per-log device_get with zero extra transfers.
                metrics.update(
                    diagnostics_metrics(
                        grads=grads, params=state.params, updates=updates
                    )
                )
        return new_state, metrics

    def _eval_step_impl(self, state: TrainState, batch: dict):
        with jax.named_scope("preprocess"):
            inputs, targets = self.task.prepare(batch, None, training=False)
        # Eval on the parameter EMA when configured (the DeiT/CaiT-recipe
        # standard: the averaged weights generalize better than the last
        # step's). The EMA tree lives in opt_state (optimizer.py
        # track_params_ema) and mirrors the params' shardings.
        params = state.params
        if self.config.ema_decay is not None:
            ema = ema_params(state.opt_state)
            if ema is not None:
                params = ema
        variables = {"params": params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        with partitioned_over(self.mesh.size):
            outputs = self.model.apply(
                variables, inputs, is_training=False,
                **self.task.apply_kwargs(targets),
            )
        with jax.named_scope("metrics"):
            return self.task.eval_sums(outputs, batch)

    # ------------------------------------------------------------- data flow

    def shard_batch(self, batch: dict) -> dict:
        """Place a host batch onto the mesh, batch dim over the data axis.

        Single-process: a plain ``device_put``. Multi-process (SPMD over
        hosts — the reference's implicit TPU-VM setup,
        input_pipeline.py:102): each process passes its *per-host* shard
        (the data pipeline already yields per-host batches) and the global
        array is assembled process-locally — no host gathers any other
        host's data.
        """

        multiprocess = jax.process_count() > 1

        def sharding_for(key, leaf):
            return self._blayout.batch_sharding(
                dim=self.task.batch_dim(key, np.ndim(leaf))
            )

        def place(key, leaf):
            sharding = sharding_for(key, leaf)
            if multiprocess:
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(leaf)
                )
            return jax.device_put(leaf, sharding)

        return {k: place(k, v) for k, v in batch.items()}

    # ------------------------------------------------------------------ loop

    def train_step(self, state: TrainState, batch: dict, rng: jax.Array):
        return self._train_step(state, self.shard_batch(batch), rng)

    def train_step_placed(self, state: TrainState, placed: dict, rng: jax.Array):
        """One jitted update on an already-placed (sharded) batch.

        The step the feeder path consumes: public surface for harnesses
        that drive placement themselves (bench.py fed modes,
        tools/feed_micro.py pair it with :meth:`shard_batch` /
        :class:`~sav_tpu.data.feeder.DeviceFeeder`). :meth:`train_step`
        is the shard-inline convenience wrapper over the same program.
        """
        return self._train_step(state, placed, rng)

    def compile_train_step(self, state: TrainState, placed: dict, rng):
        """AOT-lower + compile the train step for an already-placed batch.

        What :meth:`fit` calls at its first batch, and the public surface
        for harnesses that run the compiled executable directly and read
        its artifacts — XLA cost analysis (bench.py's MFU), HLO metadata
        for trace attribution (tools/profile_step.py's op index) — instead
        of poking the private ``_train_step``. Same program as
        :meth:`train_step_placed`; a second call with the same argument
        types is answered by jax's in-memory caches, but AOT compilation
        does not populate the jit dispatch cache, so mixing the two pays a
        second compile.
        """
        return self._train_step.lower(state, placed, rng).compile()

    def eval_step(self, state: TrainState, batch: dict):
        return self._eval_step(state, self.shard_batch(batch))

    def _pad_eval_batch(self, batch: dict, target: int) -> dict:
        """Zero-pad a partial final batch to ``target`` rows + 'valid' mask.

        Keeps eval at one compiled shape and makes any eval size work on any
        mesh (the reference hard-errored on non-divisible eval batches,
        input_pipeline.py:150-152)."""
        n = self.task.rows(batch)
        pad = target - n

        def pad_leaf(key, x):
            x = np.asarray(x)
            axis = self.task.batch_dim(key, x.ndim)
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, pad)
            return np.pad(x, widths)

        out = {k: pad_leaf(k, v) for k, v in batch.items()}
        out["valid"] = np.concatenate(
            [np.ones(n, np.float32), np.zeros(pad, np.float32)]
        )
        return out

    def evaluate(
        self,
        state: TrainState,
        eval_iter: Iterator[dict],
        *,
        recorder=None,
    ) -> dict:
        """Run one evaluation pass over ``eval_iter``.

        The loop is pipelined like fit()'s (config.async_feed): pad+place
        run on the feeder's background thread so transfer of batch N+1
        overlaps the device's batch N, and the per-batch sums stay on
        device until one ``device_get`` at the end — the old per-batch
        synchronous fetch + sync serialized every stage and inflated eval
        windows on slow-transfer rigs (PERF.md §7).

        Numerics guards mirror fit()'s: a nonfinite eval metric dumps a
        flight-recorder incident bundle (``recorder`` — fit() passes its
        own so mid-run evals share the training ring; standalone evals
        build a fresh one when ``config.record``) and, under
        ``config.debug_nans``, raises ``FloatingPointError`` naming the
        bad keys.
        """
        batch_size: Optional[int] = None
        data_div = int(np.prod([self.mesh.shape[a] for a in batch_axes(self.mesh)]))

        def place(batch: dict):
            # Runs on the feeder thread in async mode: pad the (host)
            # batch to the compiled shape, then shard onto the mesh. The
            # single feeder worker processes batches in order, so the
            # first-batch shape fixing is race-free.
            nonlocal batch_size
            n = self.task.rows(batch)
            if batch_size is None:
                # First batch fixes the compiled shape: its size rounded up
                # to a mesh-divisible multiple (so a tiny eval set shards).
                batch_size = -(-n // data_div) * data_div
            if n < batch_size:
                batch = self._pad_eval_batch(batch, batch_size)
            return self.shard_batch(batch)

        cfg = self.config
        feeder = None
        if cfg.async_feed:
            from sav_tpu.data.feeder import DeviceFeeder

            feeder = DeviceFeeder(
                iter(eval_iter), place, depth=cfg.feed_depth,
                name="eval-feeder",
            )
            placed_iter = feeder
        else:
            placed_iter = map(place, eval_iter)
        device_sums = []
        # Dispatches stay async so the device pipelines batches, but
        # run-ahead must be bounded: every dispatched-not-retired step
        # holds its input batch in HBM, and a long eval set on a
        # compute-bound device would otherwise accumulate them all. Once
        # batch K's sums are ready its inputs are free, so blocking on
        # the (N - max_inflight)-th sums caps live batches at
        # feed_depth (queued) + max_inflight (dispatched).
        max_inflight = cfg.feed_depth + 1
        retired = 0
        try:
            for placed in placed_iter:
                device_sums.append(self._eval_step(state, placed))
                if len(device_sums) - retired >= max_inflight:
                    jax.block_until_ready(  # savlint: disable=SAV101 -- run-ahead cap: retiring step N-max_inflight bounds placed-batch HBM
                        device_sums[retired]
                    )
                    retired += 1
        finally:
            if feeder is not None:
                feeder.close()
        totals: dict[str, float] = {}
        for sums in jax.device_get(device_sums):  # savlint: disable=SAV101 -- the one end-of-pass sync the whole eval loop deferred to
            for k, v in sums.items():
                totals[k] = totals.get(k, 0.0) + float(v)
        n = max(totals.get("count", 0.0), 1.0)
        results = {
            "eval_loss": totals.get("loss_sum", 0.0) / n,
            "eval_top_1_acc": totals.get("top_1_sum", 0.0) / n,
            "eval_top_5_acc": totals.get("top_5_sum", 0.0) / n,
            "eval_count": n,
        }
        bad = sorted(k for k, v in results.items() if not math.isfinite(v))
        if bad:
            if (
                recorder is None
                and cfg.record
                and jax.process_index() == 0
            ):
                # Standalone eval (train.py --eval-only): no training ring
                # exists, but a nonfinite eval loss still gets a bundle
                # (trigger + metrics + config) for the record.
                from sav_tpu.obs.recorder import FlightRecorder

                recorder = FlightRecorder.from_config(
                    cfg, cfg.log_dir or cfg.checkpoint_dir or "."
                )
            if recorder is not None:
                recorder.dump_incident(
                    "eval_nonfinite",
                    extra={"eval": results, "bad_keys": bad},
                )
            if cfg.debug_nans:
                raise FloatingPointError(
                    f"non-finite values in eval metrics: {bad}"
                )
        return results

    def _save_with_stamp(self, step: int, state: TrainState) -> None:
        """One checkpoint save + the resume stamp (docs/elasticity.md).

        ``resume.json`` persists the full mid-epoch resume recipe next to
        the checkpoints — ``(epoch, step-in-epoch, rng derivation, feeder
        position)`` — as auditable provenance: the checkpoint's own
        ``state.step`` stays authoritative (the resumable data stream and
        the rng are both pure functions of ``(seed, step)``), and the
        stamp lets supervisors/post-mortems read the resume point without
        orbax. Advisory by design: the stamp is written when the async
        save is *requested*; a preemption between request and commit
        leaves a stamp one save ahead, which readers must treat as an
        upper bound.
        """
        self.checkpointer.save(step, state)
        cfg = self.config
        spe = max(cfg.steps_per_epoch, 1)
        stamp = {
            "schema": 1,
            "step": int(step),
            "epoch": int(step // spe),
            "step_in_epoch": int(step % spe),
            "steps_per_epoch": spe,
            "seed": cfg.seed,
            # Batches consumed == steps on the EFFECTIVE schedule;
            # rewind-and-skip shifts the original-schedule position
            # (train.py's resume_schedule_position + notes.rewind_skip
            # carry the audit).
            "feeder_position": int(step),
            "rng": {
                "derivation":
                    "jax.random.fold_in(jax.random.PRNGKey(seed), 1), "
                    "then fold_in(rng, state.step) inside the step",
            },
            "saved_unix": round(time.time(), 3),
        }
        path = os.path.join(self.checkpointer.directory, "resume.json")
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(stamp, f, indent=2)
            os.replace(tmp, path)
        except OSError:
            pass  # provenance, never fatal

    def fit(
        self,
        train_iter: Iterator[dict],
        *,
        num_steps: Optional[int] = None,
        eval_iter_fn=None,
        state: Optional[TrainState] = None,
        log_fn=None,
        manifest=None,
    ) -> tuple[TrainState, list[dict]]:
        """Run the training loop.

        Args:
          train_iter: yields batches as the model's task reads them
            (sav_tpu/train/tasks.py): dicts with 'images', 'labels' and
            optional 'mix_labels'/'ratio', or with 'tokens'.
          num_steps: total steps (default: config.total_steps).
          eval_iter_fn: zero-arg callable returning a fresh eval iterator
            (fixes the reference's exhausted-generator eval bug,
            train.py:239-250 / SURVEY.md §2.9 #21).
          log_fn: callable(dict) for metrics (host-side, outside jit).
          manifest: optional :class:`~sav_tpu.obs.manifest.RunManifest`.
            The run's facts accrete onto it, on crash paths too; the
            *caller* owns terminal ok/error finalization, since a run may
            continue past fit().

        The loop and nothing else: next batch (fetched and placed by a
        background :class:`~sav_tpu.data.feeder.DeviceFeeder` under
        ``config.async_feed``, the default; else fetch -> put inline;
        docs/input_pipeline.md), dispatch, the run-ahead cap, the log
        boundary every ``log_every_steps``, the save and eval cadences.
        Each phase is a ``sav:fit/<phase>`` span (sav_tpu/obs/spans.py)
        that books its bucket on the goodput ledger (``self.last_goodput``,
        <log_dir>/goodput.json). Whatever else watches the run (incident
        recording, anomaly profiling, the hang deadline, heartbeats, cost
        and memory gauges, runtime guards, the manifest's notes) listens
        behind one seam, sav_tpu/obs/fit_observers.py;
        docs/observability.md has the table of events, listeners and the
        exit order.
        """
        from sav_tpu.obs.fit_observers import build_observers, fleet_identity

        fit_t0 = time.perf_counter()
        cfg = self.config
        num_steps = num_steps if num_steps is not None else cfg.total_steps
        state = state if state is not None else self.restore_or_init()
        # The fit() stream is derived from the run key with an explicit
        # tag, not by perturbing the seed (savlint SAV110): seed+1 could
        # collide with another run's seed, and fold_in is auditable.
        rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 1)
        history: list[dict] = []
        obs_dir = cfg.log_dir or cfg.checkpoint_dir
        # Shared telemetry files are written by FLEET process 0 only: runs
        # share --log-dir and concurrent writers would clobber each other.
        fleet_proc, fleet_procs = fleet_identity()
        obs_writer = fleet_proc == 0
        ledger = GoodputLedger()
        # One span per phase: each reaches any running profiler session as
        # ``sav:fit/<phase>``, books its bucket on the ledger, and lands in
        # the Chrome file under trace_spans.
        tracer = SpanTracer(
            os.path.join(obs_dir or ".", "spans.trace.json")
            if cfg.trace_spans and obs_writer else None,
            ledger=ledger,
        )
        start_step = int(jax.device_get(state.step))  # savlint: disable=SAV101 -- one-time read before the loop, not per-step
        observers = build_observers(
            cfg, ledger=ledger, tracer=tracer, manifest=manifest,
            obs_dir=obs_dir, identity=(fleet_proc, fleet_procs),
            checkpointer=self.checkpointer, params=state.params,
            start_step=start_step,
            layout={
                **self.layout.describe(self.mesh),
                # Always: kept for the readers of manifests that said 'flat'.
                "optimizer_layout": "per_leaf",
            },
        )
        # Compiled at the first batch, once a call; the loop calls the
        # executable, which refuses arguments it was not compiled for.
        compiled_step = None
        t_last = time.time()
        last_logged_step = start_step
        last_saved_step = None
        # Wall anchor of the checkpoint_every_secs cadence; every save
        # pushes the timer out.
        t_last_ckpt = time.time()
        # jax.profiler trace window (SURVEY.md §5): a few steady-state
        # steps, relative to start_step so resumed runs still profile.
        prof_start = start_step + cfg.profile_start_step
        prof_stop = prof_start + max(cfg.profile_num_steps, 1)
        profiling = False
        # Wall time of the current logging window attributable to training
        # compute (dispatch + log sync); booked on the ledger's step /
        # stall buckets window by window.
        window_s = 0.0

        def book_window(upto: int) -> None:
            nonlocal window_s, last_logged_step
            if ledger.note_window(upto - last_logged_step, window_s, step=upto):
                tracer.instant("fit/stall_anomaly", step=upto)
                observers.stall(upto)
            window_s = 0.0
            last_logged_step = upto

        def save(at: int) -> None:
            nonlocal last_saved_step, t_last_ckpt
            with tracer.span("fit/checkpoint", bucket="checkpoint", step=at):
                self._save_with_stamp(at, state)
            last_saved_step = at
            t_last_ckpt = time.time()

        data_iter = iter(train_iter)
        feeder = None
        if cfg.async_feed:
            # Async double-buffered device feed (sav_tpu/data/feeder.py):
            # transfer of batch N+1 overlaps the device's step N, the loop
            # only ever blocks on the bounded queue (booked as input_wait)
            # and the training thread issues no device_put. The feeder runs
            # up to feed_depth + 1 batches ahead; on preemption they are
            # dropped and re-produced by the resumable iterator, which
            # replays from the checkpointed step.
            from sav_tpu.data.feeder import DeviceFeeder

            feeder = DeviceFeeder(
                data_iter, observers.wrap_place(self.shard_batch),
                depth=cfg.feed_depth, name="train-feeder", tracer=tracer,
            )
        # Fed, placed batches arrive ready: the residual queue wait is all
        # that is left on this thread.
        batches = feeder if feeder is not None else data_iter
        # Every dispatched-not-retired step holds its placed input batch in
        # HBM; the metrics are tiny device scalars, so the deque is free.
        max_inflight = cfg.feed_depth + 1
        inflight_metrics: deque = deque()
        try:
            for step in range(start_step, num_steps):
                observers.before_step(step, state)
                if cfg.profile_dir is not None:
                    # Steps dispatch asynchronously: sync the device at both
                    # window edges so the trace covers exactly the intended
                    # steps, not a few ms of host dispatch.
                    if not profiling and prof_start <= step < prof_stop:
                        jax.block_until_ready(state)  # savlint: disable=SAV101 -- profiler window edge: trace must cover exactly the intended steps
                        profiler.start_trace(cfg.profile_dir)  # savlint: disable=SAV113 -- THE armed static window opening (profile_dir), gated to its configured edge
                        profiling = True
                    elif profiling and step >= prof_stop:
                        jax.block_until_ready(state)  # savlint: disable=SAV101 -- profiler window edge: trace must cover exactly the intended steps
                        profiler.stop_trace()  # savlint: disable=SAV113 -- THE armed static window closing at its configured edge
                        profiling = False
                with tracer.span(
                    "fit/batch_wait", bucket="input_wait", step=step + 1
                ):
                    try:
                        batch = next(batches)
                    except StopIteration:
                        break
                if feeder is not None:
                    sharded = batch
                else:
                    observers.host_batch(batch)
                    with tracer.span(
                        "fit/shard_batch", bucket="h2d", step=step + 1
                    ):
                        sharded = self.shard_batch(batch)  # savlint: disable=SAV106 -- the sanctioned serial fallback (async_feed=False)
                if compiled_step is None:
                    with tracer.span(
                        "fit/compile", bucket="compile", in_timeline=True
                    ):
                        compiled_step = self.compile_train_step(
                            state, sharded, rng
                        )
                        observers.compiled(compiled_step)
                    # Don't let compile time pollute the first throughput
                    # and MFU window.
                    t_last = time.time()
                t_step = time.perf_counter()
                with tracer.span("fit/dispatch", step=step + 1):
                    state, metrics = compiled_step(state, sharded, rng)
                # Cap dispatch run-ahead the same way evaluate() does: with
                # the feeder keeping the host fast nothing else blocks
                # before the log boundary. Waiting on the metrics of the
                # step max_inflight back retires its inputs while the queue
                # ahead stays full, so placed-batch exposure is feed_depth
                # (queued) + max_inflight (dispatched). Booked into the
                # step window: it is device-compute wait.
                inflight_metrics.append(metrics)
                if len(inflight_metrics) > max_inflight:
                    with tracer.span("fit/run_ahead_wait", step=step + 1):
                        jax.block_until_ready(  # savlint: disable=SAV101 -- run-ahead cap: device-compute wait that retires placed inputs
                            inflight_metrics.popleft()
                        )
                observers.after_step(step + 1)
                window_s += time.perf_counter() - t_step
                if step == start_step:
                    observers.first_step(state, sharded, rng)
                if cfg.debug_nans:
                    assert_all_finite(metrics, f"metrics at step {step + 1}")
                if (step + 1) % cfg.log_every_steps == 0 or step + 1 == num_steps:
                    with tracer.span("fit/log_boundary", step=step + 1):
                        t_sync = time.perf_counter()
                        with tracer.span("fit/log_sync", step=step + 1):
                            fetched = jax.device_get(metrics)  # savlint: disable=SAV101 -- the per-log-window metrics sync; priced into the step bucket
                        window_s += time.perf_counter() - t_sync
                        with tracer.span("fit/log_host", step=step + 1):
                            m = {k: float(v) for k, v in fetched.items()}
                            now = time.time()
                            m["step"] = step + 1
                            steps_since = step + 1 - last_logged_step
                            book_window(step + 1)
                            m["images_per_sec"] = (
                                cfg.global_batch_size * steps_since / max(now - t_last, 1e-9)
                            )
                            observers.log(step + 1, m, steps_since, now - t_last)
                            t_last = now
                            history.append(m)
                        if log_fn is not None:
                            with tracer.span("fit/log_fn", step=step + 1):
                                log_fn(m)
                        observers.logged(step + 1, m)
                    if self.checkpointer is not None and (
                        step + 1
                    ) != last_saved_step:
                        # Step-granular cadences (docs/elasticity.md) ride
                        # the log boundary: its sync already drained the
                        # pipeline and Orbax writes on the side. Steps
                        # SINCE the last save, not a step-number modulo
                        # (which would fire only at lcm(N, log_every_steps)
                        # when the cadences misalign): the save lands at
                        # the first boundary >= N steps after the last.
                        since_save = (step + 1) - (
                            last_saved_step
                            if last_saved_step is not None else start_step
                        )
                        if (
                            cfg.checkpoint_every_steps
                            and since_save >= cfg.checkpoint_every_steps
                        ) or (
                            cfg.checkpoint_every_secs is not None
                            and now - t_last_ckpt >= cfg.checkpoint_every_secs
                        ):
                            save(step + 1)
                if (step + 1) % cfg.steps_per_epoch == 0:
                    epoch = (step + 1) // cfg.steps_per_epoch
                    if eval_iter_fn is not None and epoch % cfg.eval_every_epochs == 0:
                        with tracer.span("fit/eval", bucket="eval", epoch=epoch):
                            em = self.evaluate(
                                state, eval_iter_fn(),
                                recorder=observers.recorder,
                            )
                        em["step"] = step + 1
                        history.append(em)
                        if log_fn is not None:
                            log_fn(em)
                    if (
                        self.checkpointer is not None
                        and epoch % cfg.checkpoint_every_epochs == 0
                        and (step + 1) != last_saved_step
                    ):
                        save(step + 1)
                    # Reset the throughput window so eval/checkpoint wall time
                    # doesn't deflate the next logged images_per_sec.
                    t_last = time.time()
                    if step + 1 != last_logged_step:
                        # steps_per_epoch is not a multiple of
                        # log_every_steps: book the steps since the last
                        # boundary now, so the ledger's per-step medians
                        # stay honest.
                        book_window(step + 1)
            if window_s:
                # StopIteration cut the run between log boundaries.
                ledger.account("step", window_s)
            observers.loop_done()
            if self.checkpointer is not None:
                if last_saved_step != num_steps:
                    save(num_steps)
                with tracer.span("fit/checkpoint_wait", bucket="checkpoint"):
                    # The hang deadline was disarmed at loop_done precisely so
                    # this final flush can take as long as the storage needs.
                    self.checkpointer.wait()  # savlint: disable=SAV123 -- bounding the final checkpoint flush would truncate the save; the hang deadline is already disarmed
        finally:
            # In the finally so that crashed runs report too: the manifest
            # carries whatever telemetry exists at the point of death.
            observers.exit(sys.exc_info()[1], state, feeder)
            if profiling:
                profiler.stop_trace()  # savlint: disable=SAV113 -- crash inside the armed static window: close it so the trace survives
            tracer.write()
        # What this call traced, lowered, compiled and loaded, beside the
        # ledger's compile bucket: the seconds split, and the cache's state.
        compiled = compile_log.summary(since=fit_t0)
        compiled = {
            "trace_lower_s": compiled["trace_lower_s"],
            "backend_compile_s": compiled["backend_compile_s"],
            "cache_load_s": compiled["cache_load_s"],
            "cache_hits": compiled["cache_hits"],
            "cache_misses": compiled["cache_misses"] + compiled["cache_off"],
        }
        self.last_goodput = {**ledger.summary(), "compile": compiled}
        if obs_dir is not None and obs_writer:
            os.makedirs(obs_dir, exist_ok=True)
            with open(os.path.join(obs_dir, "goodput.json"), "w") as f:
                json.dump(self.last_goodput, f, indent=2)
        goodput_record = {
            "step": int(jax.device_get(state.step)),  # savlint: disable=SAV101 -- post-loop summary read
            **ledger.flat_metrics(),
            **{"compile/" + k: v for k, v in compiled.items()},
        }
        history.append(goodput_record)
        if log_fn is not None:
            log_fn(goodput_record)
        return state, history
