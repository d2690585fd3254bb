#!/usr/bin/env python
"""The state-free part of the vector-decay delta rule and the rule's
operands on the live chip, at the vector-decay hybrid's cell: the two Mosaic
calls of ``sav_tpu/ops/gated_delta.py::_prepare_by_lane_in_vmem`` beside the
XLA program they replace (``_prepare_by_lane``), and the two of
``_operands_in_vmem`` beside theirs (``_operands``), each alone.

    python tools/kda_prepare_micro.py [--tiles 16,32] [--trips 1,2] [--operand-tiles 8,16] [--sums rolls,triangle]
        [--iters 20] [--rounds 4] [--out chiprun_out/kda_prepare_micro.json]

At ``[N, B, H, C, d_k] = [64, 2, 32, 64, 128]`` (one KDA layer of
``ling.train_ep64_4k``: 2 x 4,096 tokens in chunks of 64, 32 heads; q and k
bfloat16, g and beta float32) the minimum over ``--rounds`` of the mean of
``--iters`` calls (host clock to ``block_until_ready``) of:

* ``xla``: ``_prepare_by_lane`` forward, and its transpose (JAX's, under the
  function's ``jax.checkpoint``: the forward again, then the backward);
* ``calls`` at every ``--tiles`` (chunks a grid step) and ``--trips`` (pairs
  of chunks a trip of a grid step's loop): the forward and the backward
  ``pallas_call`` alone, on a ``gamma`` already summed;
* ``operands``: from the arrays a block holds (q, k and the gate's ``a`` ``[2,
  4096, 32 x 128]`` bfloat16, ``A_log``, ``dt_bias``) to q and k normalised and
  ``gamma``, chunk-major, and back: XLA's program for that arithmetic
  (``_operands``: normalise, gate, turn, sum; its transpose under its
  ``jax.checkpoint``) and the two calls at every ``--operand-tiles`` (chunks a
  grid step) and both ``--sums`` (how a chunk's rows are summed in VMEM);

the bytes each call has to move over the chip's bandwidth beside them, and the
largest difference between the kernels and the XLA program in the results and
the gradients. Not a benchmark: numbers for PERF.md's findings and for
``LANE_CHUNK_TILE``, ``_CHUNKS_A_TRIP``, ``OPERANDS_TILE`` and ``_SUM_FORM``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from conv_micro import largest_difference, timed  # noqa: E402  (beside this file: the same clock and the same measure)
from sav_tpu.ops import gated_delta as rule  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e chip (benchmark/device.py's table)
SHAPE = (64, 2, 32, 64, 128)  # chunks, batch, heads, chunk, d_k
LOWER_BOUND = -5.0  # the public config's kda_lower_bound


def operands(seed: int = 0):
    chunks, batch, heads, chunk, dk = SHAPE
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    q = (unit(jax.random.normal(keys[0], SHAPE)) * dk ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(keys[1], SHAPE)).astype(jnp.bfloat16)
    g = LOWER_BOUND * jax.nn.sigmoid(3.0 * jax.random.normal(keys[2], SHAPE))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], SHAPE[:4]))
    squares = tuple(
        jax.random.normal(key, SHAPE[:4] + (chunk,)).astype(jnp.bfloat16) for key in keys[4:]
    )
    return (q, k, g, beta), squares


def both_directions(results):
    """``(forward, transpose)`` of ``results(*operands)``, jitted; the
    transpose takes the operands and the results' cotangents."""
    return jax.jit(results), jax.jit(lambda a, cotangents: jax.vjp(results, *a)[1](cotangents))


def prepared(prepare):
    return both_directions(lambda *a: tuple(prepare(*a, 1)))


def raw_operands(seed: int = 1):
    """What a KDA block holds: q and k as its convolutions left them, the
    gate's pre-activation, the head's rates and the lanes' offsets; and
    cotangents for the chunk-major results, once a reader."""
    chunks, batch, heads, chunk, dk = SHAPE
    flat = (batch, chunks * chunk, heads, dk)
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    q, k = (jax.nn.silu(jax.random.normal(key, flat) + 0.5).astype(jnp.bfloat16) for key in keys[:2])
    a = (2.0 * jax.random.normal(keys[2], flat)).astype(jnp.bfloat16)
    a_log = jnp.log(jax.random.uniform(keys[3], (heads,), minval=0.5, maxval=4.0))
    dt_bias = jax.random.normal(keys[4], (heads * dk,))
    reader = lambda pair: tuple(jax.random.normal(key, SHAPE).astype(jnp.bfloat16) for key in pair) + (
        jax.random.normal(pair[0], SHAPE) * 1e-2,)
    return (q, k, (a, a_log, dt_bias)), (reader(keys[5:7]), reader(keys[7:9]))


def operands_micro(args, report: dict) -> None:
    """The ``operands`` part of the report."""
    chunks, batch, heads, chunk, dk = SHAPE
    (q, k, gate), cotangents = raw_operands()
    rows = chunks * batch * heads * chunk * dk  # elements of one array
    report["operands_floor_ms"] = {  # q, k, a in, q, k and gamma out; back: the same in, two cotangents of each, dq, dk, da out
        "forward": 1e3 * rows * (3 * 2 + 2 * 2 + 4) / HBM_BYTES_PER_S,
        "backward": 1e3 * rows * (3 * 2 + 2 * (2 * 2 + 4) + 3 * 2) / HBM_BYTES_PER_S,
    }
    xla_forward, xla_transpose = both_directions(lambda q, k, gate: rule._operands(q, k, gate, chunk, LOWER_BOUND)[:2])
    want = (xla_forward(q, k, gate), xla_transpose((q, k, gate), cotangents))
    report["operands_xla_ms"] = {
        "forward": 1e3 * timed(xla_forward, (q, k, gate), args.iters, args.rounds),
        "transpose": 1e3 * timed(xla_transpose, ((q, k, gate), cotangents), args.iters, args.rounds),
    }
    print(json.dumps({name: report[name] for name in ("operands_floor_ms", "operands_xla_ms")}), flush=True)
    a, a_log, dt_bias = gate
    packed = (a, jnp.exp(a_log), dt_bias)
    report["operands_kernel"] = []
    numbers = lambda text: [int(n) for n in text.split(",")]
    for sums, tile in ((sums, tile) for sums in args.sums.split(",") for tile in numbers(args.operand_tiles)):
        rule._SUM_FORM = sums
        jax.clear_caches()  # a module constant: nothing traced with the last one may stay
        one = {"sums": sums, "operands_tile": tile}
        in_vmem, in_vmem_transpose = both_directions(
            lambda q, k, gate: rule._operands_in_vmem(q, k, gate, chunk, LOWER_BOUND, tile, False)[:2])
        forward = lambda *a: rule._operands_forward(*a, chunk, LOWER_BOUND, tile, False)
        backward = lambda *a: rule._operands_backward(*a, chunk, LOWER_BOUND, tile, False)
        try:
            got = (in_vmem(q, k, gate), in_vmem_transpose((q, k, gate), cotangents))
            one["largest_difference"] = largest_difference(got, want)
            one["calls_ms"] = {
                "forward": 1e3 * timed(forward, (q, k, packed), args.iters, args.rounds),
                "backward": 1e3 * timed(backward, (q, k, packed, cotangents[0] + cotangents[1]), args.iters, args.rounds),
            }
        except Exception as e:  # a tiling Mosaic refuses: say so and go on
            one["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
        report["operands_kernel"].append(one)
        print(json.dumps(one), flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--tiles", default=str(rule.LANE_CHUNK_TILE))
    parser.add_argument("--trips", default=str(rule._CHUNKS_A_TRIP))
    parser.add_argument("--operand-tiles", default=str(rule.OPERANDS_TILE))
    parser.add_argument("--sums", default="rolls,triangle")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--rounds", type=int, default=4)
    parser.add_argument("--out", default="chiprun_out/kda_prepare_micro.json")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("kda_prepare_micro: no TPU; the kernels' times come from a chip only", file=sys.stderr)
        return 3
    chunks, batch, heads, chunk, dk = SHAPE
    systems = chunks * batch * heads
    rows, square = chunk * dk, chunk * chunk
    floor = {  # q, k in the compute dtype, gamma float32, T beta and the masked Q K^T (or their cotangents) bfloat16
        "forward": systems * (2 * rows * 2 + rows * 4 + 2 * square * 2) / HBM_BYTES_PER_S,
        "backward": systems * (4 * rows * 2 + 2 * rows * 4 + 2 * square * 2) / HBM_BYTES_PER_S,
    }
    (q, k, g, beta), cotangents = operands()
    gamma = jnp.cumsum(g, axis=-2)
    xla_forward, xla_transpose = prepared(rule._prepare_by_lane)
    want = (xla_forward(q, k, gamma, beta), xla_transpose((q, k, gamma, beta), cotangents))
    report = {
        "device": jax.devices()[0].device_kind, "shape": list(SHAPE), "systems": systems,
        "form": rule.rule_form(chunks, chunk, dk, 1, by_lane=True),  # what the rule picks here, from the live backend
        "floor_ms": {name: 1e3 * seconds for name, seconds in floor.items()},
        "xla_ms": {
            "forward": 1e3 * timed(xla_forward, (q, k, gamma, beta), args.iters, args.rounds),
            "transpose": 1e3 * timed(xla_transpose, ((q, k, gamma, beta), cotangents), args.iters, args.rounds),
        },
        "kernel": [],
    }
    print(json.dumps({name: report[name] for name in ("device", "shape", "form", "floor_ms", "xla_ms")}), flush=True)
    operands_micro(args, report)
    numbers = lambda text: [int(n) for n in text.split(",")]
    for tile, a_trip in ((tile, a_trip) for a_trip in numbers(args.trips) for tile in numbers(args.tiles)):
        rule._CHUNKS_A_TRIP = a_trip
        jax.clear_caches()  # a module constant: nothing traced with the last one may stay
        one = {"chunk_tile": tile, "pairs_a_trip": a_trip}
        forward = lambda *a: rule._prepare_by_lane_forward(*a, tile, False)
        backward = lambda *a: rule._prepare_by_lane_backward(*a, tile, False)
        rule_forward, rule_transpose = prepared(functools.partial(rule._prepare_by_lane_in_vmem, tile=tile))
        try:
            got = (rule_forward(q, k, gamma, beta), rule_transpose((q, k, gamma, beta), cotangents))
            one["largest_difference"] = largest_difference(got, want)
            one["calls_ms"] = {
                "forward": 1e3 * timed(forward, (q, k, gamma, beta), args.iters, args.rounds),
                "backward": 1e3 * timed(backward, (q, k, gamma, beta) + cotangents, args.iters, args.rounds),
            }
        except Exception as e:  # a tiling Mosaic refuses: say so and go on
            one["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
        report["kernel"].append(one)
        print(json.dumps(one), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
