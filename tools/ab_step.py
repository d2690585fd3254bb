#!/usr/bin/env python
"""A/B full train-step variants on the live chip to attribute perf deltas.

Variants (any comma list via --variants):
  base       — as-shipped defaults (plain-autodiff attention backward,
               f32 logits, per-leaf optimizer)
  bf16logits — TrainConfig.attention_logits_dtype='bfloat16' (halved L²
               softmax HBM traffic)
  nomax      — non-stabilized softmax (skip the running-max subtraction):
               one fewer full pass over the [B,H,L,L] tensor. MEASUREMENT
               ONLY — exp overflows past logits ~88, so shipping it would
               need an accuracy gate + magnitude argument.
  bhld       — attention core in [B,H,L,D] layout (transpose after the
               projections, batched matmuls, transpose back) — tests
               whether the '...qhd,...khd->...hqk' einsums' implicit
               relayouts beat explicit one-shot transposes.
  noclip     — clip_grad_norm=None: prices the global-norm pass in the
               'optimizer + rest' bucket (PERF.md §5's trace: ~8 ms).
  fused      — attention_backend='fused': the single-pass short-sequence
               kernel (sav_tpu/ops/fused_attention.py) on every attention
               core. THE r6 promotion gate: 'auto' adopts the fused
               kernel at a shape only when this full-step A/B plus the
               regression sentinel confirm the win the attn_tune
               microbench claims. Compare against the bf16logits row
               (the shipping config), not base.
  flash      — attention_backend='pallas': the online-softmax flash
               kernel, same comparison (its measured loss at model-zoo
               shapes is the reason the fused kernel exists — PERF.md §5).

Prints one line per variant: best/median step ms over N windows. Chip
throughput drifts minute-to-minute (~2x, PERF.md §5) — re-run and compare
best-of windows across orderings before trusting deltas under ~5%.
"""

from __future__ import annotations

import argparse
import statistics
import time

import jax


def time_steps(trainer, batch, warmup=3, windows=4, steps=10):
    state = trainer.init_state(0)
    batch = trainer.shard_batch(batch)
    step = trainer._train_step
    rng = jax.random.PRNGKey(0)
    for _ in range(warmup):
        state, metrics = step(state, batch, rng)
    jax.device_get(metrics["loss"])
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch, rng)
        jax.device_get(metrics["loss"])
        times.append((time.perf_counter() - t0) / steps * 1e3)
    return min(times), statistics.median(times)


def make_batch(bs, image_size):
    from sav_tpu.data import synthetic_data_iterator

    return next(
        synthetic_data_iterator(
            batch_size=bs, image_size=image_size, num_classes=1000, learnable=False
        )
    )


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--variants", default="base,bf16logits")
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--model", default="deit_s_patch16")
    args = p.parse_args()

    from sav_tpu.train import TrainConfig, Trainer
    from sav_tpu.ops import attention as att

    import jax.numpy as jnp

    known = {"base", "bf16logits", "nomax", "bhld",
             "noclip", "fused", "flash"}
    variants = args.variants.split(",")
    unknown = set(variants) - known
    if unknown:
        raise SystemExit(f"unknown variants {sorted(unknown)}; known: {sorted(known)}")

    batch = make_batch(args.batch_size, 224)

    orig_xla = att.xla_attention
    orig_softmax = att._softmax_probs
    for variant in variants:
        att.xla_attention = orig_xla
        att._softmax_probs = orig_softmax
        if variant == "nomax":

            def _nomax_probs(q, k, bias, scale, logits_dtype):
                qs = q * jnp.asarray(scale, dtype=q.dtype)
                logits = jnp.einsum(
                    "...qhd,...khd->...hqk", qs, k,
                    preferred_element_type=jnp.dtype(logits_dtype),
                )
                if bias is not None:
                    logits = logits + bias.astype(logits.dtype)
                e = jnp.exp(logits)
                return e / jnp.sum(e, axis=-1, keepdims=True)

            att._softmax_probs = _nomax_probs
        elif variant == "bhld":

            def _bhld(q, k, v, bias=None, *, scale=None, dropout_rate=0.0,
                      dropout_rng=None, deterministic=True, logits_dtype=None,
                      **kw):
                if dropout_rate > 0.0 and not deterministic:
                    raise ValueError("bhld A/B variant is deterministic-only")
                if scale is None:
                    scale = q.shape[-1] ** -0.5
                ld = jnp.dtype(logits_dtype) if logits_dtype else jnp.float32
                qt = jnp.transpose(q * jnp.asarray(scale, q.dtype), (0, 2, 1, 3))
                kt = jnp.transpose(k, (0, 2, 1, 3))
                vt = jnp.transpose(v, (0, 2, 1, 3))
                s = jnp.einsum(
                    "bhqd,bhkd->bhqk", qt, kt, preferred_element_type=ld
                )
                if bias is not None:
                    s = s + bias.astype(s.dtype)
                p = jax.nn.softmax(s, axis=-1).astype(vt.dtype)
                o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
                return jnp.transpose(o, (0, 2, 1, 3))

            att.xla_attention = _bhld
        config = TrainConfig(
            model_name=args.model,
            num_classes=1000,
            image_size=224,
            compute_dtype="bfloat16",
            attention_backend=(
                {"fused": "fused", "flash": "pallas"}.get(variant, "xla")
            ),
            # 'float32' explicitly for base: None inherits
            # the compute dtype (bf16), which would collapse base and
            # bf16logits into the same configuration. The round-4+ variants
            # (nomax/bhld/noclip/fused/flash) ride bf16 logits so their
            # deltas read against the SHIPPING config — compare them to the
            # bf16logits row, not base. (The Pallas kernels do their
            # softmax in f32 on-chip and ignore the knob; setting it keeps
            # the rest of the step identical across those rows.) Threads
            # through create_model into the blocks' logits_dtype attribute.
            attention_logits_dtype=(
                "bfloat16"
                if variant in ("bf16logits", "nomax", "bhld", "noclip",
                               "fused", "flash")
                else "float32"
            ),
            global_batch_size=args.batch_size,
            transpose_images=False,
            clip_grad_norm=None if variant == "noclip" else 1.0,
            seed=0,
        )
        trainer = Trainer(config)
        best, med = time_steps(trainer, batch)
        print(f"{variant:10s} best {best:7.2f} ms  median {med:7.2f} ms", flush=True)
    att.xla_attention = orig_xla
    att._softmax_probs = orig_softmax


if __name__ == "__main__":
    main()
