#!/usr/bin/env python
"""The expert layer's routed path on the live chip, at a cell's shapes: which
grouped matmul to run, and what the sorted path costs beside a dense loop.

    python tools/moe_micro.py [--tokens 8192] [--dim 2048] [--width 768]
        [--experts 256] [--held 16] [--top-k 8] [--skip-tilings] [--out chiprun_out/moe_micro.json]
    python tools/moe_micro.py --sum-only [--seed 0]      # reading 6 alone, at the four cells' shapes

Six readings, each forward and forward + backward (the fifth and the sixth
forward alone), minimum over rounds of the mean of ``--iters`` calls (host
clock to ``block_until_ready``):

1. the grouped matmul alone on ``[tokens x k, dim] x [held, dim, width]``
   with uniform groups (``tokens x k / experts`` rows each, the rest of the
   rows in no group): ``jax.lax.ragged_dot`` as XLA lowers it, and jax's
   megablox kernel at several tilings;
2. ``SparseMoEBlock`` (router, sort, gathers, grouped matmuls, combine,
   shared expert) as the program runs it;
3. the same layer as a dense loop: every held expert on every token, weighted
   by a ``[T]`` vector (the reference's way), with the shared expert;
4. the layer at the seeded routing with the bound's factor at 2, 4, 8 and the
   worst case (``tokens x k`` rows), and with every routing on the held
   experts (the overflow pass at its most trips); on that routing, the
   largest difference in the output and in every gradient between the
   overflow loops and buffers that hold every row;
5. the candidates for the sum of ``[bound, dim]`` weighted rows back into
   ``[tokens, dim]`` float32, on ids as the sort leaves them (ascending
   within each expert's group, half of the buffer live): a scatter-add, a
   sort of the ids with a sorted segment sum, a Mosaic kernel that adds one
   row a grid step; and the gather of as many rows, their transpose;
6. the sum the layer runs (``ops/rows_to_tokens.py``), the kernel against
   ``segment_sum``, at the four expert cells' shapes and at a live share of
   0.5 and 1.0 of the buffer, for both callers: ``moe/combine``'s forward
   (bfloat16 rows, a float32 weight a row, a float32 result) and
   ``moe/dispatch``'s backward (bfloat16 rows, a bfloat16 result); the dead
   rows hold NaN, which must not reach either result; ``tile_bounds`` alone.

Not a benchmark: numbers for PERF.md's findings and for the tiling constant
in ``sav_tpu/models/layers/moe.py``.
"""

from __future__ import annotations

import argparse
import functools
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
from sav_tpu.ops import rows_to_tokens as sums  # noqa: E402

TILINGS = [(128, 128, 128), (256, 512, 256), (512, 512, 256), (512, 1024, 256), (512, 2048, 256),
           (512, 1024, 384), (256, 1024, 768), (512, 768, 512),
           # candidates at 3,584 x 1,024 (gmm_tiling gives the first)
           (256, 896, 1024), (512, 896, 1024), (256, 1792, 1024), (256, 512, 512), (256, 896, 512)]


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


def sorted_segment_sum(rows, ids, live, tokens):
    """The ids sorted (dead rows last), the rows gathered in that order, a
    segment sum that is told so."""
    ids = jnp.where(live, ids, tokens)
    by_token = jnp.argsort(ids, stable=True)
    return jax.ops.segment_sum(
        jnp.take(rows, by_token, axis=0), jnp.take(ids, by_token), num_segments=tokens, indices_are_sorted=True
    )


def mosaic_sum(rows, ids, live, tokens):
    """One row a grid step in token order: the output block is the row's
    token's, kept in VMEM while the token stays the same. Dead rows go to a
    row past the end; tokens with no row keep the zeros the output starts as."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ids = jnp.where(live, ids, tokens)
    by_token = jnp.argsort(ids, stable=True).astype(jnp.int32)
    sorted_ids = jnp.take(ids, by_token)
    dim = rows.shape[-1]

    def kernel(by_token_ref, ids_ref, row_ref, zeros_ref, out_ref):
        del by_token_ref, zeros_ref
        r = pl.program_id(0)
        first = jnp.logical_or(r == 0, ids_ref[r] != ids_ref[jnp.maximum(r - 1, 0)])

        @pl.when(first)
        def _():
            out_ref[...] = row_ref[...]

        @pl.when(jnp.logical_not(first))
        def _():
            out_ref[...] += row_ref[...]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows.shape[0],),
            in_specs=[
                pl.BlockSpec((None, 1, dim), lambda r, by_token, ids: (by_token[r], 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, 1, dim), lambda r, by_token, ids: (ids[r], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((tokens + 1, 1, dim), jnp.float32),
        input_output_aliases={3: 0},
    )(by_token, sorted_ids, rows[:, None, :], jnp.zeros((tokens + 1, 1, dim), jnp.float32))
    return out[:tokens, 0]


# (cell, rows of the routed buffer, width, tokens, experts held): reading 6's shapes.
SUM_SHAPES = [("lfm2.train_ep8_8k", 32768, 2048, 32768, 8), ("xing.train_ep8_4k", 8192, 3584, 8192, 8),
              ("qwen3next.train_ep16_4k", 20480, 2048, 16384, 32), ("joyai.train_ep16_4k", 8192, 2048, 8192, 16)]


def sorted_groups(keys, tokens: int, group: int, count: int):
    """``(token [count], live [count], sizes)`` as the layer's sort leaves a
    buffer: a group of ``group`` ascending tokens a key, the live rows first."""
    token = jnp.concatenate(
        [jnp.sort(jax.random.permutation(k, tokens)[:group]) for k in keys]
        + [jnp.zeros((count - group * len(keys),), jnp.int32)]).astype(jnp.int32)
    return token, jnp.arange(count) < group * len(keys), jnp.full((len(keys),), group, jnp.int32)


def write(report: dict, out: str) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)


segment_sum = jax.jit(sums.sum_xla, static_argnames=("tokens", "dtype"))
tile_bounds = jax.jit(sums.tile_bounds, static_argnames=("tile", "tokens"))


def sum_alone(seed: int, iters: int, rounds: int) -> dict:
    """Reading 6: operands as the sort leaves them (``held`` groups of
    ascending tokens, the live rows first); the kernel where ``sum_form``
    takes the backend and the shape."""
    report = {}
    for cell, count, dim, tokens, held in SUM_SHAPES:
        for share in (0.5, 1.0):
            keys = jax.random.split(jax.random.PRNGKey(seed), held + 2)
            group = int(count * share) // held
            token, live, sizes = sorted_groups(keys[:held], tokens, group, count)
            rows = jnp.where(live[:, None], jax.random.normal(keys[-1], (count, dim), jnp.bfloat16), jnp.nan)
            weight = jax.random.normal(keys[-2], (count,), jnp.float32)
            form = sums.sum_form(count, tokens, dim, held, rows.dtype)
            entry = {"form": form, "live_rows": group * held}
            for caller, w, dtype in (("combine_forward", weight, jnp.float32), ("dispatch_backward", None, jnp.bfloat16)):
                xla = functools.partial(segment_sum, tokens=tokens, dtype=jnp.dtype(dtype))
                entry[caller] = {"segment_sum_ms": timed(xla, (rows, w, token, live), iters, rounds)}
                if form["sum"] != "kernel":
                    continue
                kernel = functools.partial(sums.rows_to_tokens, tokens=tokens, dtype=jnp.dtype(dtype), tile=form["tile"],
                                           unit=form["unit"], interpret=False)
                ms = timed(kernel, (rows, w, token, live, sizes), iters, rounds)
                entry[caller]["kernel_ms"] = ms
                if isinstance(ms, float):
                    got = kernel(rows, w, token, live, sizes).astype(jnp.float32)
                    want = xla(rows, w, token, live).astype(jnp.float32)
                    entry[caller]["max_abs_diff"] = float(jnp.max(jnp.abs(got - want)))
                    entry[caller]["max_abs"] = float(jnp.max(jnp.abs(want)))
            if form["sum"] == "kernel":
                bounds = functools.partial(tile_bounds, tile=form["tile"], tokens=tokens)
                entry["tile_bounds_ms"] = timed(bounds, (token, live, sizes), iters, rounds)
            report[f"{cell} [{count}, {dim}] -> {tokens}, live {share}"] = entry
            print(cell, share, json.dumps(entry), flush=True)
    return report


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
    p.add_argument("--skip-tilings", action="store_true", help="leave out reading 1's megablox tilings")
    p.add_argument("--sum-only", action="store_true", help="reading 6 alone, at the four cells' shapes")
    p.add_argument("--seed", type=int, default=0, help="reading 6's operands")
    p.add_argument("--out", default="chiprun_out/moe_micro.json")
    args = p.parse_args(argv)

    if args.sum_only:
        write({"device": jax.devices()[0].device_kind, "seed": args.seed,
               "sum_alone": sum_alone(args.seed, args.iters, args.rounds)}, args.out)
        return 0

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    rows_n = args.tokens * args.top_k
    rows = jax.random.normal(keys[0], (rows_n, args.dim), jnp.bfloat16)
    kernels = (jax.random.normal(keys[1], (args.held, args.dim, args.width)) * args.dim**-0.5).astype(jnp.bfloat16)
    group_sizes = jnp.full((args.held,), rows_n // args.experts, jnp.int32)
    report = {"device": jax.devices()[0].device_kind, "shapes": vars(args), "grouped_matmul": {}}

    variants = {"ragged_dot": lambda r, k: jax.lax.ragged_dot(r, k, group_sizes)}
    for tiling in () if args.skip_tilings else TILINGS:
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
        _, chosen, weights, _ = moe._Router(args.experts, args.top_k, 2.5).apply({"params": params["route"]}, flat, bias)
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

    # 4. The bound's factor at the seeded routing, the worst case, and the overflow pass taken.
    onto_held = jnp.where(jnp.arange(args.experts) < args.held, 10.0, 0.0)
    probe = jax.random.normal(keys[0], x.shape, jnp.float32)

    def with_factor(factor, fn):
        """``fn()`` with the bound's factor at ``factor`` (the constant is read
        when a function is traced: hand ``fn`` new functions to trace)."""
        kept, moe.ROWS_OVER_EXPECTED = moe.ROWS_OVER_EXPECTED, factor
        try:
            return fn()
        finally:
            moe.ROWS_OVER_EXPECTED = kept

    def overflowing(x, p):
        return block.apply({"params": p}, x, onto_held)[0]

    def value_and_grads(x, p):
        """Every routing on the held experts: the output, and the gradients of
        its product with a fixed probe by the input and every leaf."""
        loss = lambda x, p: jnp.sum(overflowing(x, p).astype(jnp.float32) * probe)
        return jax.jit(lambda x, p: (overflowing(x, p), jax.grad(loss, (0, 1))(x, p)))(x, p)

    worst = args.experts // args.held
    report["sorted_path_by_factor"] = {}
    for factor in sorted({moe.ROWS_OVER_EXPECTED, 4, 8, worst}):
        entry = with_factor(factor, lambda: both(lambda x, p: sorted_path(x, p), (x, params), args.iters, args.rounds))
        report["sorted_path_by_factor"][str(factor)] = entry
        print("sorted_path at factor", factor, entry, flush=True)
    report["sorted_path_worst_case_buffers"] = report["sorted_path_by_factor"][str(worst)]
    report["sorted_path_overflow_taken"] = both(overflowing, (x, params), args.iters, args.rounds)
    print("sorted_path_overflow_taken", report["sorted_path_overflow_taken"], flush=True)
    # The overflow loops against buffers that hold every row, on the same routing: what the
    # chip's grouped matmul, dynamic slices and scatter-adds give inside the loops.
    taken, held_all = value_and_grads(x, params), with_factor(worst, lambda: value_and_grads(x, params))
    gaps = jax.tree.map(
        lambda a, b: {
            "max_abs_diff": float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
            "max_abs": float(jnp.max(jnp.abs(b.astype(jnp.float32)))),
        },
        taken, held_all,
    )
    report["overflow_against_worst_case_buffers"] = {
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): gap
        for path, gap in jax.tree_util.tree_flatten_with_path(gaps, is_leaf=lambda g: "max_abs" in g)[0]
    }
    print("overflow_against_worst_case_buffers", json.dumps(report["overflow_against_worst_case_buffers"]), flush=True)

    # 5. Back to tokens.
    bound = moe.routed_row_bound(rows_n, args.held, args.experts)
    report["bound"] = bound
    group = bound // (2 * args.held)
    ids, live, sizes = sorted_groups(jax.random.split(keys[0], args.held), args.tokens, group, bound)
    weighted = jax.random.normal(keys[1], (bound, args.dim), jnp.float32)
    candidates = {
        "scatter_add": jax.jit(lambda rows: sums.sum_xla(rows, None, ids, live, args.tokens, jnp.float32)),
        "the_layers_sum": jax.jit(lambda rows: moe._tokens_of_rows(rows, None, ids, live, sizes, args.tokens, jnp.float32)),
        "sort_then_sorted_segment_sum": jax.jit(lambda rows: sorted_segment_sum(rows, ids, live, args.tokens)),
        "mosaic_row_a_step": jax.jit(lambda rows: mosaic_sum(rows, ids, live, args.tokens)),
        "gather_of_as_many_rows": jax.jit(lambda rows: moe._rows_of_tokens(rows[: args.tokens], ids, live, sizes)),
    }
    want = candidates["scatter_add"](weighted)
    report["back_to_tokens"] = {}
    for name, fn in candidates.items():
        ms = timed(fn, (weighted,), args.iters, args.rounds)
        entry = {"fwd_ms": ms}
        if isinstance(ms, float) and name != "gather_of_as_many_rows":
            entry["max_abs_diff"] = float(jnp.max(jnp.abs(fn(weighted) - want)))
        report["back_to_tokens"][name] = entry
        print(name, entry, flush=True)

    report["sum_alone"] = sum_alone(args.seed, args.iters, args.rounds)
    write(report, args.out)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
