#!/usr/bin/env python
"""The expert layer's routed path on the live chip, at a cell's shapes: which
grouped matmul to run, and what the sorted path costs beside a dense loop.

    python tools/moe_micro.py [--tokens 8192] [--dim 2048] [--width 768]
        [--experts 256] [--held 16] [--top-k 8] [--out chiprun_out/moe_micro.json]

Three readings, each forward and forward + backward, minimum over rounds of
the mean of ``--iters`` calls (host clock to ``block_until_ready``):

1. the grouped matmul alone on ``[tokens x k, dim] x [held, dim, width]``
   with uniform groups (``tokens x k / experts`` rows each, the rest of the
   rows in no group): ``jax.lax.ragged_dot`` as XLA lowers it, and jax's
   megablox kernel at several tilings;
2. ``SparseMoEBlock`` (router, sort, gathers, grouped matmuls, combine,
   shared expert) as the program runs it;
3. the same layer as a dense loop: every held expert on every token, weighted
   by a ``[T]`` vector (the reference's way), with the shared expert.

Not a benchmark: numbers for PERF.md's findings and for the tiling constant
in ``sav_tpu/models/layers/moe.py``.
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

from jax.experimental.pallas.ops.tpu import megablox  # noqa: E402

from sav_tpu.models.layers import moe  # noqa: E402

TILINGS = [(128, 128, 128), (256, 512, 256), (512, 512, 256), (512, 1024, 256), (512, 2048, 256),
           (512, 1024, 384), (256, 1024, 768), (512, 768, 512)]


def timed(fn, args, iters, rounds):
    try:
        jax.block_until_ready(fn(*args))
    except Exception as e:  # noqa: BLE001 - a tiling the compiler refuses is a reading too
        return f"{type(e).__name__}: {e}"[:200]
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e3)
    return round(best, 4)


def both(fn, args, iters, rounds):
    """``fn(*args) -> array``: forward ms, and forward + backward ms."""
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=(0, 1)))
    return {"fwd_ms": timed(jax.jit(fn), args, iters, rounds), "fwd_bwd_ms": timed(grad, args, iters, rounds)}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tokens", type=int, default=8192)
    p.add_argument("--dim", type=int, default=2048)
    p.add_argument("--width", type=int, default=768)
    p.add_argument("--experts", type=int, default=256)
    p.add_argument("--held", type=int, default=16)
    p.add_argument("--top-k", type=int, default=8)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--out", default="chiprun_out/moe_micro.json")
    args = p.parse_args(argv)

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    rows_n = args.tokens * args.top_k
    rows = jax.random.normal(keys[0], (rows_n, args.dim), jnp.bfloat16)
    kernels = (jax.random.normal(keys[1], (args.held, args.dim, args.width)) * args.dim**-0.5).astype(jnp.bfloat16)
    group_sizes = jnp.full((args.held,), rows_n // args.experts, jnp.int32)
    report = {"device": jax.devices()[0].device_kind, "shapes": vars(args), "grouped_matmul": {}}

    variants = {"ragged_dot": lambda r, k: jax.lax.ragged_dot(r, k, group_sizes)}
    for tiling in TILINGS:
        variants["gmm " + "x".join(map(str, tiling))] = (
            lambda r, k, tiling=tiling: megablox.gmm(r, k, group_sizes, r.dtype, tiling, None, None, False, False)
        )
    for name, fn in variants.items():
        report["grouped_matmul"][name] = both(fn, (rows, kernels), args.iters, args.rounds)
        print(name, report["grouped_matmul"][name], flush=True)

    x = jax.random.normal(keys[2], (2, args.tokens // 2, args.dim), jnp.bfloat16)
    bias = jnp.zeros((args.experts,), jnp.float32)
    block = moe.SparseMoEBlock(
        num_experts=args.experts, top_k=args.top_k, hidden_ch=args.width, routed_scale=2.5,
        experts_held=(0, args.held), dtype=jnp.bfloat16,
    )
    params = jax.jit(lambda: block.init(keys[3], x, bias))()["params"]

    def sorted_path(x, params):
        return block.apply({"params": params}, x, bias)[0]

    def dense_loop(x, params):
        flat = x.reshape(-1, args.dim)
        _, chosen, weights = moe._Router(args.experts, args.top_k, 2.5).apply({"params": params["route"]}, flat, bias)
        fc1, fc2 = params["experts"]["fc1"], params["experts"]["fc2"]["experts_w2"].astype(x.dtype)
        gate, up = fc1["gate_experts_w1"].astype(x.dtype), fc1["up_experts_w1"].astype(x.dtype)
        y = moe.GatedFFBlock(hidden_ch=args.width, dtype=x.dtype).apply({"params": params["shared"]}, flat)
        y = y.astype(jnp.float32)
        for e in range(args.held):
            weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
            out = (jax.nn.silu(flat @ gate[e]) * (flat @ up[e])) @ fc2[e]
            y = y + weight[:, None] * out.astype(jnp.float32)
        return y.astype(x.dtype).reshape(x.shape)

    err = jnp.max(jnp.abs(sorted_path(x, params).astype(jnp.float32) - dense_loop(x, params).astype(jnp.float32)))
    report["sorted_against_loop_max_abs_diff"] = float(err)
    for name, fn in (("sorted_path", sorted_path), ("dense_loop", dense_loop)):
        report[name] = both(fn, (x, params), args.iters, args.rounds)
        print(name, report[name], flush=True)

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
