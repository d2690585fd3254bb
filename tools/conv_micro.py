#!/usr/bin/env python
"""The causal depthwise convolution on the live chip, at the two cells'
shapes: each fused form's kernel (``sav_tpu/ops/causal_conv.py``) beside the
XLA form it replaces (``sav_tpu/models/layers/causal_conv.py``), forward and
backward, each alone.

    python tools/conv_micro.py [--blocks 1024x512,512x512] [--pieces 64x128]
        [--iters 10] [--rounds 3] [--out chiprun_out/conv_micro.json]

For ``silu`` at ``[4, 4096, 8192]`` W 4 (the hybrid decoder's cell: q, k and v
joined), ``silu_key_head`` on that cell's projection ``[4, 4096, 12288]`` read
by key head where it lies (against XLA's split, join, taps and split; the
backward with z's cotangent put in) and ``gated`` at ``[4, 8192, 3 x 2048]`` W 3
(the convolution-attention hybrid's):
the minimum over rounds of the mean of ``--iters`` calls (host clock to
``block_until_ready``) of the XLA form and of the kernel at every ``--blocks``
(rows x channels a grid step) and ``--pieces`` (rows x lanes a trip of its
loop), the bytes the call has to move over the chip's bandwidth beside each,
and the largest difference between kernel and XLA form in the value and in
every gradient. Not a benchmark: numbers for PERF.md's findings and for the
block constants in ``sav_tpu/ops/causal_conv.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from sav_tpu.models.layers import causal_conv as layers  # noqa: E402
from sav_tpu.ops import causal_conv as kernels  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e chip (benchmark/device.py's table)
SHAPES = {"silu": ((4, 4096, 8192), 4), "silu_key_head": ((4, 4096, 8192), 4), "gated": ((4, 8192, 2048), 3)}
KEY_HEAD = (16, 128, 256)  # key heads, d_k, r d_v of the hybrid decoder: [q | k | v | z] a key head


def timed(fn, args, iters: int, rounds: int) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(rounds):
        start = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / iters)
    return best


def largest_difference(got, want) -> float:
    return max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))
    )


def forms(fused: str, block_s: int, block_c: int):
    """``(xla forward, xla backward, kernel forward, kernel backward)``, each
    a jitted function of ``(operand, kernel[, cotangent])``."""
    if fused == "silu_key_head":
        xla = lambda qkvz, kernel: layers.conv_silu_joined(layers._conv_silu_xla, qkvz, kernel, *KEY_HEAD)
        return (
            jax.jit(lambda x, k: xla(x, k)[:3]), jax.jit(lambda x, k, g: jax.vjp(xla, x, k)[1](g)),
            lambda x, k: kernels.key_head_conv_silu_forward(x, k, *KEY_HEAD, block_s, False),
            lambda x, k, g: kernels.key_head_conv_silu_backward(x, k, *g, *KEY_HEAD, block_s, False),
        )
    if fused == "silu":
        xla, forward, backward = layers._conv_silu_xla, kernels.conv_silu_forward, kernels.conv_silu_backward
    else:
        xla, forward, backward = layers._gated_conv_xla, kernels.gated_conv_forward, kernels.gated_conv_backward
    return (
        jax.jit(xla), jax.jit(lambda x, k, g: jax.vjp(xla, x, k)[1](g)),
        lambda x, k: forward(x, k, block_s, block_c, False),
        lambda x, k, g: backward(x, k, g, block_s, block_c, False),
    )


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--blocks", default=f"{kernels.BLOCK_S}x{kernels.BLOCK_C}")
    parser.add_argument("--pieces", default=f"{kernels._ROWS}x{kernels._LANES}")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--out", default="chiprun_out/conv_micro.json")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("conv_micro: no TPU; the kernels' times come from a chip only", file=sys.stderr)
        return 3
    pairs = lambda text: [tuple(int(n) for n in pair.split("x")) for pair in text.split(",")]
    report = {"device": jax.devices()[0].device_kind, "readings": []}
    for fused, ((batch, seq, channels), width) in SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        operand_channels = {"silu": channels, "silu_key_head": 3 * channels // 2, "gated": 3 * channels}[fused]
        x = jax.random.normal(keys[0], (batch, seq, operand_channels), jnp.bfloat16)
        g = jax.random.normal(keys[1], (batch, seq, channels), jnp.bfloat16)
        kernel = jax.random.normal(keys[2], (width, channels)) * width ** -0.5
        array = batch * seq * channels * 2
        floor = {"forward": (operand_channels // channels + 1) * array / HBM_BYTES_PER_S,
                 "backward": (2 * operand_channels // channels + 1) * array / HBM_BYTES_PER_S}
        if fused == "silu_key_head":  # the cotangents of q, k, v and z, an array each; the gradient all of qkvz
            heads, key_ch, value_ch = KEY_HEAD
            g = tuple(jax.random.normal(key, (batch, seq, heads * ch), jnp.bfloat16)
                      for key, ch in zip(jax.random.split(keys[1], 4), (key_ch, key_ch, value_ch, value_ch)))
            floor = {"forward": 2 * array / HBM_BYTES_PER_S, "backward": 4 * array / HBM_BYTES_PER_S}
        xla_forward, xla_backward, _, _ = forms(fused, 0, 0)
        want = (xla_forward(x, kernel), xla_backward(x, kernel, g))
        reading = {"fused": fused, "shape": [batch, seq, channels], "width": width, "floor_ms": {
            k: 1e3 * v for k, v in floor.items()}, "xla_ms": {
            "forward": 1e3 * timed(xla_forward, (x, kernel), args.iters, args.rounds),
            "backward": 1e3 * timed(xla_backward, (x, kernel, g), args.iters, args.rounds)}, "kernel": []}
        for rows, lanes in pairs(args.pieces):
            kernels._ROWS, kernels._LANES = rows, lanes
            for block_s, block_c in pairs(args.blocks):
                jax.clear_caches()  # the pieces are module constants: nothing traced with the last pair may stay
                _, _, forward, backward = forms(fused, block_s, block_c)
                one = {"block_s": block_s, "block_c": block_c, "rows": rows, "lanes": lanes}
                try:
                    got = (forward(x, kernel), backward(x, kernel, g))
                    one["largest_difference"] = largest_difference(got, want)
                    one["forward_ms"] = 1e3 * timed(forward, (x, kernel), args.iters, args.rounds)
                    one["backward_ms"] = 1e3 * timed(backward, (x, kernel, g), args.iters, args.rounds)
                except Exception as e:  # a tiling Mosaic refuses: say so and go on
                    one["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
                reading["kernel"].append(one)
                print(json.dumps({"fused": fused, **one}), flush=True)
        report["readings"].append(reading)
        print(json.dumps({k: v for k, v in reading.items() if k != "kernel"}), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
