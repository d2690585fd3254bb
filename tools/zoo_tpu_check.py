#!/usr/bin/env python
"""Compile-and-run every registry model family on the real TPU, fwd+bwd.

Interpret-mode CPU tests exercise kernel *numerics*, but only the real
Mosaic/XLA-TPU compilers prove the programs build on hardware (a rank-0
VMEM store passed every CPU test and failed on-chip — see PERF.md §6).
This sweep drives one small config per family through ``create_model``
fwd+bwd per available backend and reports compile/run/nonfinite status.

``--serve`` runs the serving arm instead: AOT-lower + compile the
inference program (:func:`sav_tpu.serve.engine.build_infer_fn` — uint8
in, device-side normalize, masked logits out; the exact program the
serving engine buckets) for ONE representative per model family at the
smallest bucket, proving all seven families are servable. ``--smoke``
shrinks the configs (reduced depth, 64px inputs) so the serve arm runs
in tier-1 on CPU (tests/test_serve.py); without it the full-size check
needs the chip. ``--serve --quant-weights`` compiles + runs the int8
quantized-weights serving program instead (float init →
``quantize_params`` → AOT; docs/quantization.md) — the proof that all
seven families are servable with int8 weights.

Run: python tools/zoo_tpu_check.py            (~a few minutes; TPU)
     python tools/zoo_tpu_check.py --serve    (serving arm)
     python tools/zoo_tpu_check.py --serve --quant-weights  (int8 arm)
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# One representative per family, smallest config, reduced layers where
# the registry allows overrides. Image sizes keep token counts real
# (224² ViT grid) but trim the giant models.
CASES = [
    # (name, kwargs, image_size, backends)
    ("vit_ti_patch16", {}, 224, ("xla", "pallas")),
    ("deit_s_patch16", {}, 224, ("xla", "pallas")),
    ("vit_s_patch16_rope", {}, 224, ("xla", "pallas")),
    ("vit_moe_s_patch16_e8", {}, 224, ("xla",)),
    ("cait_xxs_24", {}, 224, ("xla", "pallas")),  # talking-heads trunk
    ("cvt-13", {}, 224, ("xla", "pallas")),
    ("ceit_t", {}, 224, ("xla", "pallas")),
    ("tnt_s_patch16", {}, 224, ("xla", "pallas")),
    ("botnet_t3", {}, 224, ("xla", "pallas")),  # fused rel-pos kernel
    ("mixer_s_patch16", {}, 224, ("xla",)),  # no attention
]


# The serving arm: one representative per model FAMILY (the acceptance
# unit for "servable" — vit covers the rope/moe/deit variants' attention
# plumbing, which the training CASES sweep separately). --smoke swaps in
# the override dict to shrink depth for the tier-1 CPU run.
SERVE_CASES = [
    # (name, smoke_overrides)
    ("vit_ti_patch16", {"num_layers": 2}),
    ("botnet_t3", {"stage_sizes": (1, 1, 1, 1)}),
    ("tnt_s_patch16", {"num_layers": 2}),
    ("ceit_t", {"num_layers": 2}),
    ("cait_xxs_24", {"num_layers": 2, "num_layers_token_only": 1}),
    ("cvt-13", {"num_layers": (1, 1, 1)}),
    ("mixer_s_patch16", {"num_layers": 2}),
]


def serve_check(
    name: str, kwargs: dict, image_size: int, batch: int,
    quant_weights: bool = False,
):
    """AOT-lower + compile + run the serving program for one family at
    one bucket; returns (loss-free) (finite, compile+run seconds).

    With ``quant_weights`` the check mirrors the engine's int8 arm
    (docs/quantization.md): init a FLOAT tree, quantize it against the
    int8_serve model's template (``quantize_params`` — per-channel
    scales next to int8 kernels), and AOT-compile THAT program — the
    proof that every family's quantized serving program builds and runs
    finite on the target backend.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sav_tpu.models import create_model
    from sav_tpu.serve.engine import build_infer_fn

    model = create_model(
        name, num_classes=10, dtype=jnp.bfloat16,
        quant="int8_serve" if quant_weights else None, **kwargs
    )
    float_model = (
        create_model(name, num_classes=10, dtype=jnp.bfloat16, **kwargs)
        if quant_weights else model
    )
    rngs = {"params": jax.random.PRNGKey(0)}
    x0 = jnp.zeros((batch, image_size, image_size, 3), jnp.bfloat16)
    variables = dict(
        jax.jit(lambda r, xx: float_model.init(r, xx, is_training=False))(
            rngs, x0
        )
    )
    params = variables.pop("params")
    batch_stats = variables.pop("batch_stats", {})
    if quant_weights:
        from sav_tpu.ops.quant import quantize_params

        template = jax.eval_shape(
            lambda r, xx: model.init(r, xx, is_training=False), rngs, x0
        )["params"]
        params = jax.jit(lambda p: quantize_params(p, template))(params)
    infer = build_infer_fn(model, jnp.bfloat16)
    abstract = {
        "images": jax.ShapeDtypeStruct(
            (batch, image_size, image_size, 3), jnp.uint8
        ),
        "valid": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    t0 = time.perf_counter()
    exe = jax.jit(infer).lower(params, batch_stats, abstract).compile()
    host = {
        "images": np.random.default_rng(0).integers(
            0, 256, (batch, image_size, image_size, 3), dtype=np.uint8
        ),
        "valid": np.ones((batch,), np.float32),
    }
    logits = jax.device_get(exe(params, batch_stats, host))
    dt = time.perf_counter() - t0
    finite = bool(np.isfinite(logits).all())
    return finite, dt


def check(name: str, kwargs: dict, image_size: int, backend: str, batch: int):
    import jax
    import jax.numpy as jnp

    from sav_tpu.models import create_model

    x = jax.random.normal(
        jax.random.PRNGKey(0), (batch, image_size, image_size, 3), jnp.bfloat16
    )
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, 10)
    model = create_model(
        name, num_classes=10, dtype=jnp.bfloat16, backend=backend, **kwargs
    )
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    # Jit the init: eager init dispatches one device op per layer, and each
    # eager dispatch compiles and launches on its own — for deep conv
    # trunks (botnet_t3) hundreds of them. One traced compile replaces
    # them all.
    variables = dict(
        jax.jit(lambda r, xx: model.init(r, xx, is_training=False))(rngs, x)
    )
    params = variables.pop("params")
    # Zero-init heads make fresh logits vacuous; randomize before grads.
    if "head" in params and "kernel" in params["head"]:
        params["head"]["kernel"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), params["head"]["kernel"].shape, jnp.float32
        )

    def loss_fn(p):
        out = model.apply(
            {"params": p, **variables},
            x,
            is_training=True,
            rngs={
                "dropout": jax.random.PRNGKey(3),
                "stochastic_depth": jax.random.PRNGKey(4),
            },
            **({"mutable": list(variables)} if variables else {}),
        )
        logits = out[0] if variables else out
        onehot = jax.nn.one_hot(y, 10)
        return -jnp.mean(
            jnp.sum(jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot, -1)
        )

    t0 = time.perf_counter()
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    loss = float(jax.device_get(loss))
    # One fused on-device reduction + one transfer, not one per grad leaf
    # (each eager leaf check is its own dispatch and transfer).
    from sav_tpu.utils.debug import global_norm_nonfinite

    finite = not bool(jax.device_get(jax.jit(global_norm_nonfinite)(grads)))
    dt = time.perf_counter() - t0
    return loss, finite, dt


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--only", default=None, help="substring filter on model name")
    p.add_argument(
        "--serve", action="store_true",
        help="serving arm: AOT-compile the inference program for one "
        "representative per family at the smallest bucket (batch 1)",
    )
    p.add_argument(
        "--smoke", action="store_true",
        help="with --serve: shrink configs (2-ish layers, 64px) so the "
        "sweep runs in tier-1 on CPU",
    )
    p.add_argument(
        "--quant-weights", action="store_true",
        help="with --serve: compile + run the int8 quantized-weights "
        "serving program (float init -> quantize_params -> AOT) for "
        "every family — the docs/quantization.md servability proof",
    )
    args = p.parse_args()
    if args.quant_weights and not args.serve:
        p.error("--quant-weights is a serving arm; pass --serve too")

    if args.serve:
        image_size = 64 if args.smoke else 224
        arm = "serve:int8" if args.quant_weights else "serve"
        failures = 0
        for name, smoke_overrides in SERVE_CASES:
            if args.only and args.only not in name:
                continue
            kwargs = smoke_overrides if args.smoke else {}
            try:
                finite, dt = serve_check(
                    name, kwargs, image_size, batch=1,
                    quant_weights=args.quant_weights,
                )
                status = "OK " if finite else "NONFINITE"
                print(
                    f"{status} {arm} {name:20s} aot-compile+run {dt:.1f}s",
                    flush=True,
                )
                failures += 0 if finite else 1
            except Exception:
                failures += 1
                print(f"FAIL {arm} {name:20s}", flush=True)
                traceback.print_exc()
        print(f"\n{'ALL SERVABLE' if failures == 0 else f'{failures} FAILURES'}")
        raise SystemExit(1 if failures else 0)

    failures = 0
    for name, kwargs, image_size, backends in CASES:
        if args.only and args.only not in name:
            continue
        for backend in backends:
            try:
                loss, finite, dt = check(name, kwargs, image_size, backend, args.batch)
                status = "OK " if finite else "NONFINITE"
                print(
                    f"{status} {name:24s} {backend:6s} loss={loss:.4f} "
                    f"compile+run {dt:.1f}s",
                    flush=True,
                )
                failures += 0 if finite else 1
            except Exception:
                failures += 1
                print(f"FAIL {name:24s} {backend:6s}", flush=True)
                traceback.print_exc()
    print(f"\n{'ALL OK' if failures == 0 else f'{failures} FAILURES'}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
