"""What a train step feeds its model and scores it by.

``Trainer`` owns the loop, the gradient, the optimizer, the sharding and
the spans; a task owns the part of the step that faces the model: the
dummy input ``init`` traces with, what becomes of a batch before the model
sees it, the loss, and the metrics the log boundary fetches. A model's
registry entry names its task (``sav_tpu.models.registry.model_task``):

- ``image``: uint8 or float images ``[B, S, S, 3]`` with integer labels,
  label-smoothed (and mixed) cross-entropy, top-1 / top-5;
- ``tokens``: int32 ids ``[B, S + 1]``, next-token loss at every position
  of a looped language model (:func:`looped_lm_loss`);
- ``tokens_mtp``: the same batches, next-token loss plus a multi-token-
  prediction module's (:func:`mtp_lm_loss`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax

from sav_tpu.utils.metrics import cross_entropy, topk_correct


class ImageClassification:
    """Batches ``{"images", "labels"[, "mix_labels", "ratio"]}``; the model
    returns logits ``[B, classes]``."""

    def __init__(self, config, compute_dtype):
        self.config = config
        self.compute_dtype = compute_dtype
        if config.device_preprocess:
            # Host ships post-augment uint8; normalize + the augment
            # string's mixes run inside the jitted steps
            # (sav_tpu/ops/preprocess.py). Parsed once — the spec is
            # static, baked into the trace.
            from sav_tpu.data.augment_spec import parse_augment_spec

            self._mix_spec = parse_augment_spec(config.augment)
        else:
            self._mix_spec = None

    def dummy_input(self, batch: int) -> jax.Array:
        s = self.config.image_size
        return jnp.zeros((batch, s, s, 3), self.compute_dtype)

    def rows(self, batch: dict) -> int:
        return len(batch["labels"])

    def batch_dim(self, key: str, ndim: int) -> int:
        """The axis that counts examples in the batch's leaf ``key`` of
        ``ndim`` axes: the last for HWCN images."""
        transposed = key == "images" and self.config.transpose_images
        return ndim - 1 if transposed and ndim >= 4 else 0

    def _prep_images(self, images: jax.Array) -> jax.Array:
        if images.dtype == jnp.uint8:
            # uint8 batches belong to device_preprocess=True (which
            # normalizes on device); a plain astype here would silently
            # train on unnormalized 0..255 values (ADVICE r3). Trace-time
            # check — dtypes are static under jit.
            raise ValueError(
                "got uint8 images with device_preprocess=False; either set "
                "TrainConfig.device_preprocess=True or feed normalized "
                "float batches (load(device_preprocess=...) must match the "
                "trainer)"
            )
        if self.config.transpose_images and images.ndim == 4:
            # HWCN → NHWC (the reference's double-transpose trick lands the
            # device-side transpose here, train.py:80).
            images = jnp.transpose(images, (3, 0, 1, 2))
        return images.astype(self.compute_dtype)

    def _label_probs(self, batch: dict) -> jax.Array:
        labels = batch["labels"]
        onehot = jax.nn.one_hot(labels, self.config.num_classes, dtype=jnp.float32)
        if "mix_labels" in batch:
            ratio = batch["ratio"].astype(jnp.float32)[:, None]
            mix = jax.nn.one_hot(
                batch["mix_labels"], self.config.num_classes, dtype=jnp.float32
            )
            onehot = ratio * onehot + (1.0 - ratio) * mix
        if self.config.label_smoothing > 0.0:
            onehot = optax.smooth_labels(onehot, self.config.label_smoothing)
        return onehot

    def _device_preprocess(self, batch: dict, rng, training: bool) -> dict:
        """uint8 host batch → mixed (train) + normalized compute-dtype
        images, on device (TrainConfig.device_preprocess; see
        sav_tpu/ops/preprocess.py for the host-parity contract)."""
        from sav_tpu.ops import preprocess as pp

        images = batch["images"]
        if images.dtype != jnp.uint8:
            # The device_preprocess contract ships post-augment 0..255
            # uint8 (load(device_preprocess=True) / savrec
            # normalize=False); an already-normalized float batch here
            # would be normalized twice — silently wrong training
            # (ADVICE r3). Trace-time check: dtypes are static under jit.
            raise ValueError(
                "device_preprocess=True expects uint8 batches from the "
                f"matching pipeline mode, got {images.dtype}; feed "
                "load(device_preprocess=True) / "
                "savrec_train_iterator(normalize=False) batches, or turn "
                "device_preprocess off"
            )
        if self.config.transpose_images and images.ndim == 4:
            images = jnp.transpose(images, (3, 0, 1, 2))  # HWCN → NHWC
        batch = dict(batch)
        if training and self._mix_spec is not None and self._mix_spec.mixes:
            images, mix_labels, ratio = pp.apply_mixes(
                rng, images, batch["labels"], self._mix_spec
            )
            if mix_labels is not None:
                batch["mix_labels"] = mix_labels
                batch["ratio"] = ratio
        batch["images"] = pp.normalize_images(images, self.compute_dtype)
        return batch

    def prepare(self, batch: dict, step_rng, training: bool):
        """``(inputs, targets)`` for the model and the loss: NHWC images in
        the compute dtype and, in training, the label distributions. Both
        split along axis 0 under gradient accumulation. ``step_rng`` is
        None in eval."""
        if self.config.device_preprocess:
            # Dedicated fold so the mix draws are independent of the
            # dropout/stochastic-depth streams split from step_rng.
            rng = jax.random.fold_in(step_rng, 0x6D69) if training else None
            batch = self._device_preprocess(batch, rng, training=training)
            images = batch["images"]  # already NHWC, compute dtype
        else:
            images = self._prep_images(batch["images"])
        return images, (self._label_probs(batch) if training else None)

    def apply_kwargs(self, targets) -> dict:
        return {}

    def loss(self, logits, label_probs) -> jax.Array:
        return cross_entropy(logits, label_probs)

    def train_metrics(self, logits, batch: dict) -> dict:
        acc = topk_correct(logits.astype(jnp.float32), batch["labels"])
        return {
            "top_1_acc": jnp.mean(acc["top_1_acc"]),
            "top_5_acc": jnp.mean(acc["top_5_acc"]),
        }

    def eval_sums(self, logits, batch: dict) -> dict:
        logits = logits.astype(jnp.float32)
        labels = batch["labels"]
        onehot = jax.nn.one_hot(labels, self.config.num_classes, dtype=jnp.float32)
        n = labels.shape[0]
        # 'valid' marks real rows in a padded final batch (evaluate() pads
        # remainders so every batch has one static, mesh-divisible shape).
        valid = batch.get("valid")
        if valid is None:
            valid = jnp.ones((n,), jnp.float32)
        acc = topk_correct(logits, labels)
        logp = jax.nn.log_softmax(logits, axis=-1)
        per_example_loss = -jnp.sum(onehot * logp, axis=-1)
        return {
            "loss_sum": jnp.sum(per_example_loss * valid),
            "top_1_sum": jnp.sum(acc["top_1_acc"] * valid),
            "top_5_sum": jnp.sum(acc["top_5_acc"] * valid),
            "count": jnp.sum(valid),
        }


def exit_distribution(exit_logit: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(p, log p)`` over the passes (last axis) from the gates' logits:
    ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` for ``t < T`` and ``p_T =
    prod_{j<T}(1 - lambda_j)``, ``lambda = sigmoid(logit)``; the last pass's
    gate is not used. Sums to 1 by construction; computed in log space."""
    stay = jax.nn.log_sigmoid(-exit_logit)  # log(1 - lambda_t)
    stayed = jnp.cumsum(stay, axis=-1) - stay  # sum over j < t
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(exit_logit[..., :-1]) + stayed[..., :-1], stayed[..., -1:]],
        axis=-1,
    )
    return jnp.exp(log_p), log_p


def looped_lm_loss(ce: jax.Array, exit_logit: jax.Array, beta: float):
    """The entropy-regularised objective of arXiv:2510.25741: the mean over
    positions of ``sum_t p_t CE_t - beta H(p)``. ``ce`` and ``exit_logit``
    are ``[..., T]``. Returns ``(loss, p, entropy per position)``."""
    p, log_p = exit_distribution(exit_logit)
    entropy = -jnp.sum(p * log_p, axis=-1)
    per_position = jnp.sum(p * ce, axis=-1) - beta * entropy
    return jnp.mean(per_position), p, entropy


class TokenPrediction:
    """Batches ``{"tokens": int32 [B, S + 1]}``: the model reads the first
    ``S`` ids and is given ids 1..S as ``targets``; it scores every position
    itself and returns cross-entropies, never the full logits. Documents are
    concatenated without a boundary mask. What the token tasks share; a
    subclass adds ``loss``, ``train_metrics`` and ``eval_sums``."""

    # Any length traces the same parameters: the families have no position table.
    dummy_length = 16

    def __init__(self, config, compute_dtype):
        del compute_dtype
        if config.label_smoothing or config.device_preprocess:
            raise ValueError(
                "the token task has no label smoothing and no image "
                "preprocessing: set label_smoothing=0, device_preprocess=False"
            )
        self.config = config

    def dummy_input(self, batch: int) -> jax.Array:
        return jnp.zeros((batch, self.dummy_length), jnp.int32)

    def rows(self, batch: dict) -> int:
        return len(batch["tokens"])

    def batch_dim(self, key: str, ndim: int) -> int:
        return 0

    def prepare(self, batch: dict, step_rng, training: bool):
        del step_rng, training
        tokens = batch["tokens"]
        return tokens[:, :-1], tokens[:, 1:]

    def apply_kwargs(self, targets) -> dict:
        return {"targets": targets}

    @staticmethod
    def _weighted_sums(per_position: jax.Array, batch: dict) -> dict:
        valid = batch.get("valid")
        if valid is None:
            valid = jnp.ones((per_position.shape[0],), jnp.float32)
        weights = jnp.broadcast_to(valid[:, None], per_position.shape)
        return {"loss_sum": jnp.sum(per_position * weights), "count": jnp.sum(weights)}


class LoopedTokenPrediction(TokenPrediction):
    """Next-token loss of every pass of a looped language model at every
    position. The model (``sav_tpu/models/ouro.py``) returns each pass's
    cross-entropy and exit-gate logit; the loss is :func:`looped_lm_loss`."""

    # H(p)'s weight (arXiv:2510.25741's pre-training objective; the value is
    # assumed: benchmark/configs/ouro_2.6b.json).
    entropy_weight = 0.1

    def loss(self, outputs: dict, targets) -> jax.Array:
        del targets  # the model has already scored every position
        return looped_lm_loss(outputs["ce"], outputs["exit_logit"], self.entropy_weight)[0]

    def train_metrics(self, outputs: dict, batch: dict) -> dict:
        _, p, entropy = looped_lm_loss(
            outputs["ce"], outputs["exit_logit"], self.entropy_weight
        )
        passes = outputs["ce"].shape[-1]
        metrics = {"exit_entropy": jnp.mean(entropy), "tokens": jnp.float32(p[..., 0].size)}
        for t in range(passes):
            metrics[f"loss_ut{t + 1}"] = jnp.mean(outputs["ce"][..., t])
            metrics[f"exit_p{t + 1}"] = jnp.mean(p[..., t])
        return metrics

    def eval_sums(self, outputs: dict, batch: dict) -> dict:
        _, p, _ = looped_lm_loss(outputs["ce"], outputs["exit_logit"], self.entropy_weight)
        return self._weighted_sums(jnp.sum(p * outputs["ce"], axis=-1), batch)


def mtp_lm_loss(ce: jax.Array, ce_mtp: Optional[jax.Array], mtp_weight: float):
    """``mean CE_main + lambda mean CE_mtp`` (arXiv:2412.19437 eq. 25): the
    main head over all ``S`` positions, the multi-token-prediction module
    over the ``S - 1`` that have a next-but-one token (the model leaves the
    last one's term at 0). Returns ``(loss, main, mtp)``; a model built
    without the module gives no ``ce_mtp``, and then ``(main, main, None)``."""
    main = jnp.mean(ce)
    if ce_mtp is None:
        return main, main, None
    mtp = jnp.sum(ce_mtp) / (ce_mtp.shape[0] * (ce_mtp.shape[1] - 1))
    return main + mtp_weight * mtp, main, mtp


class MTPTokenPrediction(TokenPrediction):
    """Next-token loss plus the multi-token-prediction module's, of a decoder
    with routed experts (``sav_tpu/models/joyai.py``). The model returns
    ``ce``, ``ce_mtp`` where it has the module, each sequence's routing
    counts (all, and those on the experts it holds), each routed layer's
    fill of its bounded buffers and, where its residual path is
    hyper-connected streams, how far its mixing matrices are from doubly
    stochastic and what they do to the streams' norm; where its token mixers
    are delta-rule and gated attention blocks, the smallest decay, the largest
    state and the gate's mean; where they are short convolutions, the largest
    RMS of a block's gated result; where they are vector-decay delta-rule
    blocks, the smallest log-decay and the largest state; where the router
    picks inside groups, the share of tokens whose groups reach the experts
    held."""

    # lambda of the MTP loss (arXiv:2412.19437 section 4.2's first phase;
    # assumed: benchmark/configs/joyai_llm_flash.json).
    mtp_weight = 0.3
    # What a decoder's layers hand over of themselves, each one number a row,
    # and how the rows of a step (one a device under data parallelism) become
    # one. Hyper-connected streams: the largest |row or column sum - 1| of any
    # mixing matrix, and the largest norm of a sublayer's mixed streams over
    # the norm of the streams it mixed (the constraint holds it at 1 or
    # under). A hybrid decoder: the smallest exp(g_t) of the delta-rule
    # layers, the largest RMS of any head's final state, and the mean of the
    # gated attention's sigmoid gate. Short convolutions: the largest RMS of
    # any block's and sequence's C * c (the block is cubic and holds no norm).
    # Vector-decay layers: the smallest g of the step (a log-decay a key lane,
    # bounded below by the safe gate's lower bound) and the largest state.
    # Group-limited routing: the share of tokens whose kept groups include a
    # group of the experts held, the mean over the routed layers.
    layer_stats = (
        ("hc_doubly_stochastic_err", jnp.max), ("hc_stream_gain", jnp.max),
        ("gdn_decay_min", jnp.min), ("gdn_state_rms_max", jnp.max), ("attn_gate_mean", jnp.mean),
        ("attn_gate_mean_window", jnp.mean), ("attn_gate_mean_full", jnp.mean),
        ("sconv_out_rms_max", jnp.max),
        ("kda_decay_min", jnp.min), ("kda_state_rms_max", jnp.max), ("moe_groups_held", jnp.mean),
    )

    def loss(self, outputs: dict, targets) -> jax.Array:
        del targets  # the model has already scored every position
        return mtp_lm_loss(outputs["ce"], outputs.get("ce_mtp"), self.mtp_weight)[0]

    def train_metrics(self, outputs: dict, batch: dict) -> dict:
        _, main, mtp = mtp_lm_loss(outputs["ce"], outputs.get("ce_mtp"), self.mtp_weight)
        load = jnp.sum(outputs["moe_counts"], axis=0)  # [R, E]: the step's routings
        optional = {} if mtp is None else {"loss_mtp": mtp}
        for name, reduce in self.layer_stats:
            if name in outputs:
                optional[name] = reduce(outputs[name])
        return {
            "loss_main": main,
            **optional,
            "moe_held_share": jnp.sum(outputs["moe_held"]) / jnp.sum(load),
            # Routed layer applications that took the exact overflow pass
            # (their rows on the held experts passed the buffers' bound), and
            # the fullest one's rows over that bound.
            "moe_overflow_share": jnp.mean(outputs["moe_rows_over_bound"][0] > 1.0),
            "moe_rows_over_bound": jnp.max(outputs["moe_rows_over_bound"]),
            "moe_load_max_over_mean": jnp.max(jnp.max(load, axis=-1) / jnp.mean(load, axis=-1)),
            "moe_bias_abs_max": jnp.max(outputs["moe_bias_abs_max"]),
            "tokens": jnp.float32(outputs["ce"].size),
        }

    def eval_sums(self, outputs: dict, batch: dict) -> dict:
        return self._weighted_sums(outputs["ce"], batch)


TASKS = {
    "image": ImageClassification,
    "tokens": LoopedTokenPrediction,
    "tokens_mtp": MTPTokenPrediction,
}


def make_task(name: str, config, compute_dtype):
    if name not in TASKS:
        raise ValueError(f"unknown task {name!r}; available: {', '.join(sorted(TASKS))}")
    return TASKS[name](config, compute_dtype)
