#!/usr/bin/env python
"""Attention block-config autotuner: sweep (block_q, block_kv, block_b) per
shape across the xla / fused / flash backends on the live chip and emit the
machine-readable shape→config cache the ``auto`` dispatcher consumes
(``sav_tpu/ops/attn_tuning.py``). Grew out of ``tools/flash_sweep.py`` +
``tools/attn_micro.py`` (both retired into this).

Methodology = docs/benchmarking.md Traps 1–3, inherited from attn_micro:

- every timing loop threads the PRIMAL through the scan carry
  (``q_i = q + carry``) so XLA cannot hoist the op out of the scan;
- fwd+bwd loops tie the COTANGENT to the loop-varying output
  (``g = cot + sum(out)·1e-30``) so the algebraic simplifier cannot
  collapse the backward matmuls;
- all feasible variants compile up front, timing windows interleave
  round-robin with a rotated start order, and per-variant minima are
  reported (a one-chip machine shares its host's CPU cores, so windows
  vary).

A config that fails to build (the Mosaic VMEM rejections flash_sweep used
to die on, e.g. block_b 16/32 at DeiT shapes) is recorded as
``infeasible`` in the output cache — with the compiler's message — and the
sweep continues; configs the VMEM estimator rules out up front are
recorded without paying the compile.

Output: one JSON cache (``--out``, default
``.tpu_results/attn_tune_cache.json``; ``--merge`` folds into an existing
file so per-shape runs accumulate). Promote a sweep to the dispatcher by
pointing ``SAV_ATTN_TUNE_CACHE`` / ``TrainConfig.attention_tune_cache`` /
``bench.py --attn-tune-cache`` at it — after the full-step ``ab_step`` +
regression-sentinel gate confirms the win (docs/benchmarking.md).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _REPO_ROOT)

# sav_tpu.ops.__init__ re-exports *functions* named flash_attention /
# fused_attention that shadow the submodules on `from ... import`; go via
# importlib.
flmod = importlib.import_module("sav_tpu.ops.flash_attention")
fumod = importlib.import_module("sav_tpu.ops.fused_attention")
from sav_tpu.ops import attention as att  # noqa: E402
from sav_tpu.ops import attn_tuning  # noqa: E402


def timing_loop(fn, iters):
    """The jitted scan timing loop; the primal rides the carry (Trap 1).
    Exposed separately from :func:`make_loop` so the tier-1 methodology
    test can assert on its jaxpr (every backward-feeding matmul must be
    carry-reachable — i.e. not hoistable out of the scan)."""

    @jax.jit
    def loop(*a):
        def body(carry, _):
            q = a[0] + carry.astype(a[0].dtype)
            out = fn(q, *a[1:])
            return jnp.sum(out.astype(jnp.float32)) * 1e-30, None

        tot, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return tot

    return loop


def make_loop(fn, args, iters):
    loop = timing_loop(fn, iters)
    jax.device_get(loop(*args))  # compile + warm (and surface Mosaic errors)
    return lambda: jax.device_get(loop(*args))


def grad_wrap(fn, cot, cot_on_lanes=False):
    """fwd+bwd callable whose cotangent is tied to the output (Trap 2);
    ``cot_on_lanes``: ``cot`` is held as ``[B, H, D, L]`` (:func:`on_lanes`)."""

    def run(q, k, v):
        out, vjp = jax.vjp(fn, q, k, v)
        tied = jnp.transpose(cot, (0, 3, 1, 2)) if cot_on_lanes else cot
        g = (tied + jnp.sum(out.astype(jnp.float32)) * 1e-30).astype(out.dtype)
        dq, dk, dv = vjp(g)
        if dk.shape != dq.shape:  # fewer key/value heads than query heads
            return sum(jnp.sum(x.astype(jnp.float32)) for x in (dq, dk, dv))
        if dv.shape != dq.shape:  # a value head of its own size
            return jnp.concatenate([dq + dk, dv], axis=-1)
        return dq + dk + dv

    return run


def on_lanes(fn):
    """``fn`` on operands (and, through :func:`grad_wrap`, a cotangent) held
    as ``[B, H, D, L]``, the order in which a model's projections and rotary
    leave q, k and v (the sequence on the lanes): the transposes back to
    ``[B, L, H, D]`` meet the in-place kernels' own and XLA copies nothing.
    The gradients leave as the kernels write them."""
    return lambda *held: fn(*(jnp.transpose(x, (0, 3, 1, 2)) for x in held))


def _parse_shape(spec: str):
    parts = list(map(int, spec.split(",")))
    if len(parts) == 4:
        b, l, h, d = parts
        return b, l, l, h, d
    if len(parts) in (5, 6):  # the sixth is the value head where it differs
        return tuple(parts)
    raise ValueError(f"shape must be B,L,H,D, B,Lq,Lkv,H,D or B,Lq,Lkv,H,D,Dv — got {spec!r}")


def variant_specs(b, lq, lkv, h, d, *, blocks, block_bs, backends, itemsize,
                  causal=False, dv=None, kv_heads=None, window=None):
    """Yield (name, backend, config, builder) for every candidate; builder
    returns the (q, k, v) -> out callable. Configs the VMEM estimator
    rules out are yielded with builder=None (recorded infeasible for free).
    The fused kernel has no causal arm and one head size: it is left out
    of a sweep that asks for either, or for grouped heads (``kv_heads``).
    Under a ``window`` each pair of blocks is swept in the form the kernel's
    own rule gives it (``flash_attention.band_form``) and, where that is the
    resident pair (which has no ``block_b``), in the causal kernels' banded
    arm as well, pinned for the variant's compile.
    """
    bh = b * h
    dv = d if dv is None else dv
    kv_heads = h if kv_heads is None else kv_heads
    if "xla" in backends:
        yield "xla", "xla", None, lambda: (
            lambda q, k, v: att.xla_attention(q, k, v, causal=causal, window=window)
        )
    if "fused" in backends and not causal and dv == d and kv_heads == h:
        for bq, _ in blocks:
            for bb in block_bs:
                if b % bb != 0:  # the fused kernel's cells hold batch elements
                    continue
                cfg = {"block_q": bq, "block_kv": None, "block_b": bb}
                name = f"fused bq={bq} bb={bb}"
                if (
                    fumod.fused_vmem_bytes(
                        lq, lkv, d, heads=h, block_q=bq, block_b=bb,
                        itemsize=itemsize,
                    )
                    > fumod.FUSED_VMEM_BUDGET
                ):
                    yield name, "fused", cfg, None
                    continue
                yield name, "fused", cfg, (
                    lambda bq=bq, bb=bb: lambda q, k, v: fumod.fused_attention(
                        q, k, v, block_q=bq, block_b=bb
                    )
                )
    if "pallas" in backends:
        band_window = flmod.effective_window(window, causal, lq)
        for bq, bkv in blocks:
            build = (
                lambda bq=bq, bkv=bkv: lambda q, k, v: flmod.flash_attention(
                    q, k, v, block_q=bq, block_kv=bkv, causal=causal, window=window
                )
            )
            band = flmod.band_form(
                lq, lkv, d, dv, heads=h, kv_heads=kv_heads, window=band_window, block_q=bq, block_kv=bkv,
                itemsize=itemsize,
            )
            if band == "resident":
                cfg = {"block_q": bq, "block_kv": bkv, "block_b": None, "band": band}
                yield f"pallas bq={bq} bkv={bkv} band={band}", "pallas", cfg, build
            for bb in block_bs:
                if bh % bb != 0:
                    continue
                # Each form of the blocked backward the shape can run: the
                # one the kernel's own rule picks, and where that is the
                # one-kernel form, the two kernels as well (pinned for the
                # variant's compile, as block_b is).
                form = flmod.backward_form(
                    lq, lkv, d, dv, batch_heads=bh, block_q=bq, block_kv=bkv,
                    block_b=bb, itemsize=itemsize,
                )
                for bwd_form in dict.fromkeys((form, "two_kernels")):
                    cfg = {"block_q": bq, "block_kv": bkv, "block_b": bb, "backward": bwd_form}
                    if band:
                        cfg["band"] = "skipped_cells"
                    yield f"pallas bq={bq} bkv={bkv} bb={bb} bwd={bwd_form}", "pallas", cfg, build


class _pin_flash:
    """Pin the flash kernel's internal block_b choice, and its backward's
    two-kernel form where the variant asks for it, for the duration of a
    variant's COMPILE (make_loop traces fwd AND bwd inside this scope —
    the backward's own _pick_block_b call and its choice of form at
    vjp-trace time must see the swept values too, not the defaults).
    Likewise the banded arm of the causal kernels where the band's rule
    would give the resident pair (``skipped_cells``). A no-op for
    block_b=None and the rules' own forms."""

    def __init__(self, bb, two_kernels=False, skipped_cells=False):
        self.bb, self.two_kernels, self.skipped_cells = bb, two_kernels, skipped_cells

    def __enter__(self):
        self.orig = flmod._pick_block_b, flmod.ONE_KERNEL_VMEM_BUDGET, flmod.BAND_MAX_UNROLLED_TILES
        if self.skipped_cells:
            flmod.BAND_MAX_UNROLLED_TILES = -1
        if self.bb is not None:
            bb = self.bb
            flmod._pick_block_b = (
                lambda bh_, *, force_one=False: 1 if force_one else bb
            )
        if self.two_kernels:
            flmod.ONE_KERNEL_VMEM_BUDGET = 0
        return self

    def __exit__(self, *exc):
        flmod._pick_block_b, flmod.ONE_KERNEL_VMEM_BUDGET, flmod.BAND_MAX_UNROLLED_TILES = self.orig
        return False


def sweep_shape(shape, *, blocks, block_bs, backends, iters, rounds,
                dtype=jnp.bfloat16, bwd=True, log=print, causal=False,
                kv_heads=None, window=None, sequence_on_lanes=False):
    """Measure one shape; returns (results, infeasible) lists. With
    ``sequence_on_lanes`` the operands and the cotangent are held where a
    model's projections leave them (:func:`on_lanes`); else in their default
    order, and a kernel that wants another pays its copies inside its time."""
    b, lq, lkv, h, d = shape[:5]
    dv = shape[5] if len(shape) == 6 else d
    kv_heads = h if kv_heads is None else kv_heads
    rng = np.random.default_rng(0)

    def draw(length, heads, dim, as_dtype):
        held = (b, heads, dim, length) if sequence_on_lanes else (b, length, heads, dim)
        return jnp.asarray(rng.standard_normal(held), dtype=as_dtype)

    q, k, v = draw(lq, h, d, dtype), draw(lkv, kv_heads, d, dtype), draw(lkv, kv_heads, dv, dtype)
    cot = draw(lq, h, dv, jnp.float32)
    held = on_lanes if sequence_on_lanes else (lambda fn: fn)

    results, infeasible, loops = [], [], {}
    for name, backend, cfg, build in variant_specs(
        b, lq, lkv, h, d, blocks=blocks, block_bs=block_bs,
        backends=backends, itemsize=jnp.dtype(dtype).itemsize,
        causal=causal, dv=dv, kv_heads=kv_heads, window=window,
    ):
        if build is None:
            infeasible.append({
                "backend": backend, **(cfg or {}),
                "error": "VMEM estimate over budget (fused_vmem_bytes)",
            })
            log(f"  {name:46s} INFEASIBLE (vmem estimate)")
            continue
        pin_bb = (cfg or {}).get("block_b") if backend == "pallas" else None
        two_kernels = (cfg or {}).get("backward") == "two_kernels"
        skipped_cells = (cfg or {}).get("band") == "skipped_cells"
        try:
            fn = build()
            entry = {"name": name, "backend": backend, "config": cfg}
            with _pin_flash(pin_bb, two_kernels, skipped_cells):
                entry["_fwd"] = make_loop(held(fn), (q, k, v), iters)
                if bwd:
                    entry["_bwd"] = make_loop(
                        held(grad_wrap(fn, cot, sequence_on_lanes)), (q, k, v), iters
                    )
            loops[name] = entry
        except Exception as e:  # noqa: BLE001 — a bad config must not kill the sweep
            infeasible.append({
                "backend": backend, **(cfg or {}),
                "error": f"{type(e).__name__}: {e}"[:300],
            })
            log(f"  {name:46s} INFEASIBLE ({type(e).__name__})")

    # Round-robin interleave with rotated start (Trap 3); per-variant minima.
    keys = [
        (name, which)
        for name in loops
        for which in (("_fwd", "_bwd") if bwd else ("_fwd",))
        if which in loops[name]
    ]
    best = {kk: float("inf") for kk in keys}
    for r in range(rounds if keys else 0):  # every config infeasible → record, not crash
        for kk in keys[r % len(keys):] + keys[: r % len(keys)]:
            name, which = kk
            t0 = time.perf_counter()
            loops[name][which]()
            best[kk] = min(best[kk], (time.perf_counter() - t0) / iters * 1e3)

    for name, entry in loops.items():
        res = {
            "name": name,
            "backend": entry["backend"],
            "config": entry["config"],
            "fwd_ms": round(best[(name, "_fwd")], 3),
            "fwd_bwd_ms": (
                round(best[(name, "_bwd")], 3) if (name, "_bwd") in best else None
            ),
        }
        results.append(res)
        log(
            f"  {name:46s} fwd {res['fwd_ms']:8.3f} ms"
            + (
                f"   fwd+bwd {res['fwd_bwd_ms']:8.3f} ms"
                if res["fwd_bwd_ms"] is not None
                else ""
            )
        )
    return results, infeasible


def pick_winner(results, *, bwd=True):
    """Best variant by fwd+bwd (the training criterion) when measured,
    else fwd."""
    metric = "fwd_bwd_ms" if bwd else "fwd_ms"
    scored = [r for r in results if r.get(metric) is not None]
    return min(scored, key=lambda r: r[metric]) if scored else None


def winner_entry(winner, source: str) -> dict:
    cfg = winner.get("config") or {}
    return {
        "backend": winner["backend"],
        "block_q": cfg.get("block_q"),
        "block_kv": cfg.get("block_kv"),
        "block_b": cfg.get("block_b"),
        "fwd_ms": winner["fwd_ms"],
        "fwd_bwd_ms": winner.get("fwd_bwd_ms"),
        "source": source,
    }


def main(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument(
        "--shapes", default="256,197,6,64;64,785,6,64",
        help="semicolon-separated B,L,H,D (or B,Lq,Lkv,H,D)",
    )
    p.add_argument(
        "--backends", default="xla,fused,pallas",
        help="comma subset of xla,fused,pallas",
    )
    p.add_argument("--blocks", default="128,128;256,256;512,512",
                   help="semicolon-separated block_q,block_kv pairs")
    p.add_argument("--block-b", default="1,2,4,8,16",
                   help="comma list of batch*head slices per grid cell")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--fwd-only", action="store_true",
                   help="skip the fwd+bwd loops (winner then picked on fwd)")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--causal", action="store_true",
                   help="sweep the causal core (its entries are keyed .causal)")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="key/value heads, where fewer than the shape's query heads (grouped-query attention)")
    p.add_argument("--window", type=int, default=None,
                   help="with --causal: a sliding window's positions (entries keyed .causal.window<W>)")
    p.add_argument("--sequence-on-lanes", action="store_true",
                   help="hold q, k, v and the cotangent as [B, H, D, L], where a model's projections "
                   "leave them: the in-place kernels then pay no layout copy, as inside a train step")
    p.add_argument(
        "--out", default=".tpu_results/attn_tune_cache.json",
        help="shape→config cache to write (the dispatcher-consumable JSON)",
    )
    p.add_argument(
        "--merge", action="store_true",
        help="fold this sweep's entries into an existing --out cache",
    )
    p.add_argument(
        "--star-batch", action="store_true", default=True,
        help="also key each winner under the batch-wildcard (B*) so one "
        "measured shape covers every batch sharing its geometry",
    )
    p.add_argument("--no-star-batch", dest="star_batch", action="store_false")
    args = p.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"attn_tune: WARNING — backend is {backend!r}; kernels run in "
            "interpreter mode and timings are NOT chip-meaningful (the "
            "emitted cache should not be promoted to the dispatcher)",
            file=sys.stderr,
        )
    dtype = jnp.dtype(args.dtype)
    blocks = [tuple(map(int, bq_bkv.split(","))) for bq_bkv in args.blocks.split(";")]
    block_bs = [int(x) for x in args.block_b.split(",")]
    backends = args.backends.split(",")
    device = getattr(jax.devices()[0], "device_kind", backend)

    entries, infeasible_all = {}, {}
    for spec in args.shapes.split(";"):
        shape = _parse_shape(spec)
        b, lq, lkv, h, d = shape[:5]
        dv = shape[5] if len(shape) == 6 else d
        print(f"== shape B={b} Lq={lq} Lkv={lkv} H={h} on {args.kv_heads or h} D={d} Dv={dv} "
              f"causal={args.causal} window={args.window} ({dtype.name})", flush=True)
        results, infeasible = sweep_shape(
            shape, blocks=blocks, block_bs=block_bs, backends=backends,
            iters=args.iters, rounds=args.rounds, dtype=dtype,
            bwd=not args.fwd_only, causal=args.causal, kv_heads=args.kv_heads,
            window=args.window, sequence_on_lanes=args.sequence_on_lanes,
        )
        window = flmod.effective_window(args.window, args.causal, lq)
        key = attn_tuning.shape_key(b, lq, lkv, h, d, dtype, args.causal, dv, window)
        if infeasible:
            infeasible_all[key] = infeasible
        winner = pick_winner(results, bwd=not args.fwd_only)
        if winner is None:
            print("  (no feasible variant)", flush=True)
            continue
        asked = "".join(
            f" {flag} {value}" for flag, value in (("--kv-heads", args.kv_heads), ("--window", args.window))
            if value is not None
        ) + (" --sequence-on-lanes" if args.sequence_on_lanes else "")
        src = (
            f"tools/attn_tune.py{asked} on {device} "
            f"({'fwd' if args.fwd_only else 'fwd+bwd'} min of "
            f"{args.rounds}x{args.iters})"
        )
        entries[key] = winner_entry(winner, src)
        if args.star_batch:
            entries[attn_tuning.shape_key("*", lq, lkv, h, d, dtype, args.causal, dv, window)] = (
                winner_entry(winner, src + f" at B={b}")
            )
        print(f"  -> winner: {winner['name']}", flush=True)

    cache = attn_tuning.write_cache(
        args.out, entries, infeasible_all, device=str(device),
        merge=args.merge,
    )
    print(json.dumps({
        "out": args.out,
        "entries": len(cache["entries"]),
        "infeasible_shapes": len(cache["infeasible"]),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
