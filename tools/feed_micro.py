#!/usr/bin/env python
"""Feed microbench: how much input time does the async feeder hide?

A/B of the two fit() feed modes over the same host stream and the same
jitted train step (ISSUE 2):

  serial — fetch → shard_batch (device_put) → step, one thread (the
           pre-feeder loop; ``--no-async-feed``)
  feeder — DeviceFeeder places batch N+1 on a background thread while the
           device runs step N (the default fit() path)

Reports one JSON line: per-step times for both arms, the overlap
efficiency (what fraction of the serial arm's exposed host+h2d time the
feeder hid), and bytes/batch on the wire — run with and without
``--uint8`` to see the wire-format lever (uint8 ≈ ¼ of f32, ½ of bf16).

``--host-ms`` injects a deterministic per-batch host latency so the
harness demonstrates overlap even on rigs where the real host stream is
faster than the device step (a laptop CPU run); leave it 0 to measure
your actual pipeline balance.

CPU-safe: a virtual-device run measures real overlap of
real device_puts, just at CPU scale.

Usage:
  python tools/feed_micro.py
  python tools/feed_micro.py --uint8 --host-ms 20
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _host_iter(batch_size, image_size, uint8, host_ms, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    while True:
        if host_ms:
            time.sleep(host_ms / 1e3)
        if uint8:
            images = rng.integers(
                0, 256, (batch_size, image_size, image_size, 3), np.uint8
            )
        else:
            images = rng.standard_normal(
                (batch_size, image_size, image_size, 3)
            ).astype(np.float32)
        labels = rng.integers(0, 10, (batch_size,), np.int32)
        yield {"images": images, "labels": labels}


def _make_trainer(args):
    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name=args.model,
        num_classes=10,
        image_size=args.image_size,
        compute_dtype="float32",
        global_batch_size=args.batch_size,
        transpose_images=False,
        device_preprocess=args.uint8,
        augment="none",
        feed_depth=args.depth,
        # The two arms drive placement/step directly; config.async_feed is
        # irrelevant here (fit() is not involved).
        model_overrides={"num_layers": 2, "embed_dim": 64, "num_heads": 4},
        seed=0,
    )
    return Trainer(config)


def _timed_arm(steps, next_placed, step_fn, sync):
    t0 = time.perf_counter()
    for _ in range(steps):
        step_fn(next_placed())
    sync()
    return (time.perf_counter() - t0) / steps


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="vit_ti_patch16")
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--image-size", type=int, default=32)
    parser.add_argument("--steps", type=int, default=12)
    parser.add_argument("--depth", type=int, default=2)
    parser.add_argument(
        "--host-ms", type=float, default=0.0,
        help="injected per-batch host latency (0 = the raw generator)",
    )
    parser.add_argument(
        "--uint8", action="store_true",
        help="uint8 on the wire + device-side normalize "
        "(TrainConfig.device_preprocess) instead of f32 batches",
    )
    args = parser.parse_args(argv)

    import jax

    from sav_tpu.data.feeder import DeviceFeeder

    trainer = _make_trainer(args)
    state_holder = {"state": trainer.init_state()}
    rng = jax.random.PRNGKey(0)

    def step_fn(placed):
        state_holder["state"], m = trainer.train_step_placed(
            state_holder["state"], placed, rng
        )
        state_holder["metrics"] = m

    def sync():
        float(jax.device_get(state_holder["metrics"]["loss"]))

    first = next(_host_iter(args.batch_size, args.image_size, args.uint8, 0))
    bytes_per_batch = sum(getattr(v, "nbytes", 0) for v in first.values())
    # Warmup/compile outside both timed arms.
    step_fn(trainer.shard_batch(first))
    sync()

    # Serial arm: the training thread pays fetch + device_put in line.
    it = _host_iter(args.batch_size, args.image_size, args.uint8, args.host_ms)
    serial_s = _timed_arm(
        args.steps, lambda: trainer.shard_batch(next(it)), step_fn, sync
    )

    # Feeder arm: fetch + device_put ride the background thread.
    it = _host_iter(args.batch_size, args.image_size, args.uint8, args.host_ms)
    feeder = DeviceFeeder(
        it, trainer.shard_batch, depth=args.depth, name="feed-micro"
    )
    try:
        feeder_s = _timed_arm(args.steps, lambda: next(feeder), step_fn, sync)
        stats = feeder.stats()
    finally:
        feeder.close()

    # Host+h2d time the serial arm exposes per step, from the feeder arm's
    # own worker counters (same stream, same puts). Efficiency = the share
    # of it the feeder actually hid. >1 rounds to 1 (measurement noise).
    exposed_s = (stats["fetch_s"] + stats["h2d_s"]) / max(stats["batches"], 1)
    hidden_s = serial_s - feeder_s
    overlap_efficiency = (
        min(max(hidden_s / exposed_s, 0.0), 1.0) if exposed_s > 0 else 0.0
    )
    print(json.dumps({
        "metric": f"{args.model} feed overlap (bs={args.batch_size}, "
        f"{'uint8' if args.uint8 else 'f32'} wire, depth {args.depth}, "
        f"host+{args.host_ms:g}ms, {args.steps} steps)",
        "serial_step_ms": round(serial_s * 1e3, 2),
        "feeder_step_ms": round(feeder_s * 1e3, 2),
        "speedup": round(serial_s / feeder_s, 3) if feeder_s > 0 else None,
        "overlap_efficiency": round(overlap_efficiency, 3),
        "exposed_host_h2d_ms_per_step": round(exposed_s * 1e3, 2),
        "bytes_per_batch": bytes_per_batch,
        "feeder": stats,
        "platform": jax.devices()[0].platform,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
